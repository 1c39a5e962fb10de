"""Share of the packed batch's edge stream that is real half-edges (the
program's `batch.edge_fill` gauge): round 0 streams the whole padded
list."""


def read(run):
    fill = run.setup_info.get("edge_fill")
    return None if fill is None else 100.0 * fill
