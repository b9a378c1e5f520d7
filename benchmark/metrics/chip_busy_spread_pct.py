"""How unevenly the chips of a mesh are kept busy: the busiest device's
busy seconds less the idlest one's, over the traced window, in percent.
A ``model`` shard that a Zipf-hot key range loads harder than its
neighbour makes a straggler and shows here; every collective then waits
for it. One device plane has no spread to read."""


def read(run: dict):
    trace = run["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    busy = trace.get("busy_s_per_device") or []
    if len(busy) < 2:
        return None
    return 100.0 * (max(busy) - min(busy)) / trace["window_s"]
