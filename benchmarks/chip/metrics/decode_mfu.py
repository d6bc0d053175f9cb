"""Whole step: model FLOPs of the tokens generated in the window
(matmuls, and attention over each token's own context) per second, as a
share of the chip's bf16 peak."""


def read(run):
    if run.peak is None:
        return None
    flops = 0.0
    for r in run.reqs:
        base = len(r.prompt_ids)
        for k, t in enumerate(r.token_t):
            if run.t0 <= t < run.t1:
                flops += run.counts.decode_flops(run.model, base + k + 1)
    if flops <= 0:
        return None
    return 100.0 * flops / run.seconds / run.peak["bf16_flops_per_s"]
