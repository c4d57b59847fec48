"""Device time per step of the flash-attention kernels (ops
``flash_attn_fwd``, ``flash_attn_bwd_dkv``, ``flash_attn_bwd_dq``): 0.0
where none ran, as in a program whose attention runs in XLA."""

PATTERN = r"\bflash_attn_(fwd|bwd_dkv|bwd_dq)\b"


def read(ctx):
    return ctx.per_step_ms(PATTERN) or 0.0
