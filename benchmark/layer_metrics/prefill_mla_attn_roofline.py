"""Share of the chip's peak arithmetic rate that the prefill attention
kernel (``mx_prefill_attn``) reaches over the traced prefills of a
configuration with latent attention, whose prefill attends in EXPANDED form:
every head over keys of ``qk_nope_head_dim + qk_rope_head_dim`` and values of
``v_head_dim``. Least time: the operations of the causal band over each
prompt's REAL tokens, every latent layer (``latent_rows_read`` on the traced
``mx.decode.prefill`` spans; ``flops_ling.latent_prefill_flops``) over the
peak bf16 rate; kernel time: the summed device time of the
``mx_prefill_attn`` operations in the same trace (it computes the padded rung
in float32 at one head size for keys and values, so the share is of what the
algorithm needs, not of what the kernel does). A traced span in which no
prompt was prefilled reads nothing."""
import flops
import flops_ling
import trace_reduce
import trace_within

KERNEL = r"^%?mx_prefill_attn\b"


def read(run):
    cell, trace = run["cell"], run["trace"]
    model = cell.config["model"]
    if trace is None or cell.peaks is None or "kv_lora_rank" not in model:
        return None
    rows = [r for r in trace_within.span_args(
        run, ("mx.decode.prefill",)) or () if r.get("latent_rows_read")]
    seconds, count = trace_reduce.time_matching(trace, KERNEL)
    if not rows or not count:
        return None
    ops = sum(flops_ling.latent_prefill_flops(r["latent_rows_read"], model)
              for r in rows)
    return flops.share_of_peak(ops / cell.peaks["bf16_flops_per_s"], seconds,
                               "prefill_mla_attn_roofline")
