"""Share of its roofline that the fused canvas program reaches: the least
seconds for the bytes and operations the traced refits need (work
function, bound named in the run's log) over the device seconds of the
programs the configuration names (``work_programs``), from the trace's
program line. There is no new kernel: the fused program is the unit."""


def read(run: dict):
    trace, work = run["trace"], run["work"]
    if (not trace or not work or not run["traced_jobs"]
            or "program_least_s" not in work):
        return None
    names = tuple(run["config"].get("work_programs", ()))
    dev_s = sum(s for name, s in trace["programs"].items()
                if name.startswith(names)) / trace["devices"]
    if dev_s <= 0:
        return None
    return 100.0 * work["program_least_s"] * run["traced_jobs"] / dev_s
