"""Device time per step of the LM head and loss (scope ``lm_head``:
final norm, tied logits, log-softmax, NLL), forward and backward."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, part="lm_head")
