"""Model FLOPs utilization of the whole step: the model FLOPs of the
window's samples (``step_flops`` of the configuration's reference module;
for ``dense_decoder``, 6 per matmul parameter per token, LM head included,
plus causal attention inside each sample; no padding, no recomputation)
over window seconds x chips x the chip's published bf16 peak
(``bench/harness/peaks.py``).  None off a chip with a published peak."""


def read(ctx):
    if ctx.peaks is None:
        return None
    return 100.0 * ctx.model_flops() / (
        ctx.window_s * ctx.chips * ctx.peaks["bf16_flops"])
