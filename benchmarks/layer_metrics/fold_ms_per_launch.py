"""Device time of one `timeseries` launch (a TraceQL-metrics fold over one
block): `scan_ms_per_launch`'s join of `tempo/kernel:launch` annotations to
the device's modules, for op `timeseries`. Nothing where no such launch ran
in the traced interval or the program writes no such annotation."""
from benchmarks.layer_metrics import scan_ms_per_launch


def read(ctx):
    return scan_ms_per_launch.read(ctx, op="timeseries")
