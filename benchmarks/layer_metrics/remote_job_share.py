"""Share of the window's jobs that ran outside the process that serves HTTP
(`dispatch.jobs` of /status/kernels: `remote` over `local` + `remote`)."""
from benchmarks.lib import readers as R


def read(ctx):
    local = R.delta(ctx, "dispatch", "jobs", "local")
    remote = R.delta(ctx, "dispatch", "jobs", "remote")
    if local is None or remote is None or not local + remote:
        return None
    return 100.0 * remote / (local + remote)
