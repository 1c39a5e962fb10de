"""Device memory of the plan the solve program takes, in MB (10^6 bytes):
the program's `plan.device_bytes` gauge (edge list and tiling arrays)."""


def read(run):
    b = run.setup_info.get("plan_device_bytes")
    return None if b is None else b / 1e6
