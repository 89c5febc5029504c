"""Device time per coarse step in which ops other than the stream-collide
kernel ran (the ghost-fill gathers and scatters, layout copies): the
window's busy time less the kernel's, from the trace."""

from bench import trace


def read(rec):
    if rec.trace is None or not rec.coarse_steps or not rec.trace.devices:
        return None
    other = rec.trace.busy_s - rec.trace.op_seconds(trace.is_kernel)
    return 1e3 * other / rec.coarse_steps
