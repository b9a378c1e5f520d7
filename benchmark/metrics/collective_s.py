"""Seconds a chip spends in collective operations per job: the self time of
every device operation of the traced jobs that is a collective — an
``all-reduce``, ``all-gather``, ``reduce-scatter``, ``all-to-all`` or
``collective-permute``, whole or as its ``-start`` / ``-done`` halves —
summed over the device planes (``run["trace"]["ops"]``, keyed by the HLO
line), over the device count and the traced jobs. Self time: a collective
inside the step's block loop or the replay scan is its own event under the
``while``, and the loop's event is less its children. A trace of one device
plane, or of a program without a collective, has nothing to read.

What one real trace showed (TPU v5 lite x 4, jax 0.9.0, the (2,2) fit at
2^30 rows; PERF.md section 6, PR 28): on the ``XLA Ops`` line the
collectives are whole events, named by their HLO lines with the operands
typed — ``%all-reduce.26 = s32[1048576]{0:T(1024)S(1)} all-reduce(s32[
1048576]{...} %broadcast_select_fusion.10), channel_id=4, replica_groups=
[2,2]<=[4], ...``, ``%all-reduce.27 = (f32[1048576,1]{...}, f32[1048576,1]
{...}) all-reduce(...)`` (a tuple), ``%all-gather.7 = s32[6815744]{...}
all-gather(s32[3407872]{...} %custom-call.32), ..., frontend_attributes=
{async_collective_name="all-gather-start"}`` — and no ``-start`` /
``-done`` event; a ``sort`` or a ``fusion`` that takes ``%all-gather.7``
as an operand is not one.
"""

import re

#: the operation of an ``XLA Ops`` event's name — the HLO line
#: ``%name = shape op(operands...)``; a tuple shape holds spaces, so the
#: operation is found as the word before the first ``(`` that follows `` = ``
COLLECTIVE = re.compile(
    r"(?:^|[ )])(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")


def is_collective(op_name: str) -> bool:
    head = op_name.split(" = ", 1)[-1]
    return bool(COLLECTIVE.search(head))


def read(run: dict):
    trace = run["trace"]
    if not trace or trace["devices"] < 2 or not run["traced_jobs"]:
        return None
    total = sum(s for name, s in trace["ops"].items()
                if is_collective(name))
    if total <= 0:
        return None
    return total / trace["devices"] / run["traced_jobs"]
