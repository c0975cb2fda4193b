"""SIGKILL after the window, restart on the same directories: seconds from
the restart to /ready (device start-up and WAL replay), by the benchmark's
clock."""


def read(ctx):
    return ctx["extras"].get("restart_ready_s")
