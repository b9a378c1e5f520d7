"""The stack of the device cache before the replay scan, per job: the
program's ``replay_stack`` span (``jnp.stack`` over the cached chunks, a
second HBM copy; host seconds until the stack is enqueued), mean over the
window's jobs. A fit that replays nothing has nothing to read."""

from benchmark.metrics._program_spans import mean_of


def read(run: dict):
    return mean_of(run, lambda job: job["sum"].get("replay_stack"))
