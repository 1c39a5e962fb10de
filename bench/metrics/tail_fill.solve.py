"""Share of the COO tail's padded entries that are real (the program's
`plan.tail_entries` over `plan.tail_capacity` gauges): phase ② streams the
whole padded tail every round."""


def read(run):
    entries = run.setup_info.get("tail_entries")
    capacity = run.setup_info.get("tail_capacity")
    if entries is None or not capacity:
        return None
    return 100.0 * entries / capacity
