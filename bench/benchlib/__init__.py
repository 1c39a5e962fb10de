"""The benchmark's own library: spec loading, traffic, trace reduction,
statistics and the peak table.  It imports nothing of the program under
test; the system adapters under `bench/systems/` are the only files that do.
"""
