"""Device time of one `select` launch (the top-k over a group's per-trace
masks and keys): `scan_ms_per_launch`'s join of `tempo/kernel:launch`
annotations to the device's modules, for op `select`. Nothing where no such
launch ran in the traced interval."""
from benchmarks.layer_metrics import scan_ms_per_launch


def read(ctx):
    return scan_ms_per_launch.read(ctx, op="select")
