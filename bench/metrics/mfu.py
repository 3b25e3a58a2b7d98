"""Model operations of the window's tokens (two per active weight per
prompt and generated token, attention over the live context, the output
head where logits are used) over the window times the chips' bf16 peak."""
from bench.counts import model_flops


def read(run):
    s = run.stats
    if not s.tokens or run.peaks is None:
        return None
    flops = model_flops(run.dims, s.prefill_tokens, s.decode_ctx)
    return 100.0 * flops / (s.seconds * run.chips * run.peaks["bf16_flops"])
