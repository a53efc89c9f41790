"""Device time of the operations named ``mx_paged_attn`` (the paged
attention kernel's ``pallas_call``) per run of the decode step program
(``mx_decode_step``) on the lead device in the traced window."""
import trace_reduce

KERNEL = r"^%?mx_paged_attn\b"
STEP = "mx_decode_step"


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    seconds, count = trace_reduce.time_matching(trace, KERNEL)
    steps = sum(1 for name, _s, _e in trace["modules"] if STEP in name)
    if not count or not steps:
        return None
    return 1e3 * seconds / steps
