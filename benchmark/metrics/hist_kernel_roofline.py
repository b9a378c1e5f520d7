"""Share of its roofline that the histogram kernel reaches: the least
seconds for the operations and bytes of the traced jobs' kernel calls as the
kernel is written (work function, bound named in the run's log) over the
device seconds of the operations the configuration names (``kernel_ops``),
from the trace's operation line."""


def read(run: dict):
    trace, work = run["trace"], run["work"]
    names = tuple(run["config"].get("kernel_ops", ()))
    if not trace or not work or not names or not run["traced_jobs"]:
        return None
    # an operation's name is its whole HLO line: match what stands before
    # " = ", not the operands, which name other operations
    dev_s = sum(s for name, s in trace["ops"].items()
                if any(n in name.split(" = ")[0] for n in names)
                ) / trace["devices"]
    if dev_s <= 0 or "kernel_least_s" not in work:
        return None
    return 100.0 * work["kernel_least_s"] * run["traced_jobs"] / dev_s
