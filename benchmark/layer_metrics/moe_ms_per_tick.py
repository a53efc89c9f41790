"""Device time of the expert layer's named kernels (``mx_moe_gmm``, the
grouped product over the held experts, and ``mx_moe_shared``, the shared
expert) inside the runs of the decode step program, per run. The router's
XLA operations carry no name of their own in a device trace and are not in
it."""
import trace_within

KERNELS = r"^%?mx_moe_\w+\b"
STEP = "mx_decode_step"


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    seconds, count, steps = trace_within.time_within(trace, KERNELS, STEP)
    if not count or not steps:
        return None
    return 1e3 * seconds / steps
