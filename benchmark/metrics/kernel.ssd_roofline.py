"""The Mamba-2 mixers' state-space core against the chip's roofline: the
chunked scan's four products at the configuration's chunk, forward and two
gradient products, and the bytes of ``W_in``'s output and of the gated
``y`` read and written (``costs_ssd.ssd_core``, the larger of the two
bounds), for the tokens the program ran through each scan
(``dl4j_ssm_tokens_total``, a step's mean over the window), over the device
time of the events under ``attn/ssd``: the taps, the steps, the scan, the
skip, the gate and the norm. The two projections beside it are dense
products of ``attn`` (``kernel.dense_roofline``); a checkpointed layer's
recomputed forward is time, not work. How much of that time the chunked
scan takes (``attn/ssd/scan``) goes to standard error."""
import costs
import costs_ssd
import scope_reduce
import span_reduce


def read(ctx):
    ms = scope_reduce.scope_ms(ctx, costs_ssd.SCOPE)
    tokens = scope_reduce.by_layer(ctx, costs_ssd.TOKENS)
    steps = ctx["window"]["steps"]
    if not ms or not tokens or not steps:
        return None
    kw = ctx["cell"]["config"]["builder"]["kwargs"]
    least = sum(costs.least_seconds(
        *costs_ssd.ssd_core(n / steps, kw["seq_len"], kw["ssm_heads"],
                            kw["ssm_head_dim"], kw["ssm_groups"],
                            kw["ssm_state"], kw["ssm_chunk"]), ctx["peak"])
        for n in tokens.values())
    scan = scope_reduce.scope_ms(ctx, costs_ssd.SCAN_SCOPE) or 0.0
    span_reduce.log(f"attn/ssd: {ms:.3f} ms a step, of which the chunked "
                    f"scan (attn/ssd/scan) {scan:.3f}; required at the "
                    f"roofline {1e3 * least:.3f}")
    return costs.Share(least_s=least, device_s=ms / 1e3)
