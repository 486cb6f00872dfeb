"""Model FLOPs of the program's ``make_vit`` net: a multiply-add counts 2,
forward per sample.  Backward is twice the forward.  LayerNorm, softmax,
GELU and the residual adds are left out, as elementwise work."""


def _sizes(hw, patch, channels):
    grid = hw // patch
    return grid * grid, grid * grid + 1, patch * patch * channels


def attn_flops(hw=224, channels=3, patch=16, width=768, depth=12, heads=12,
               mlp_width=3072, n_classes=10):
    """Attention of every block: the q, k, v and output projections, the
    logits Q K^T and the weighted sum P V (over all heads)."""
    _, tokens, _ = _sizes(hw, patch, channels)
    proj = 2 * tokens * width * 4 * width
    scores = 2 * 2 * tokens * tokens * width
    return depth * (proj + scores)


def mlp_flops(hw=224, channels=3, patch=16, width=768, depth=12, heads=12,
              mlp_width=3072, n_classes=10):
    _, tokens, _ = _sizes(hw, patch, channels)
    return depth * 2 * 2 * tokens * width * mlp_width


def forward_flops(hw=224, channels=3, patch=16, width=768, depth=12,
                  heads=12, mlp_width=3072, n_classes=10):
    n_patches, _, pdim = _sizes(hw, patch, channels)
    args = dict(hw=hw, channels=channels, patch=patch, width=width,
                depth=depth, heads=heads, mlp_width=mlp_width,
                n_classes=n_classes)
    return (2 * n_patches * pdim * width + attn_flops(**args)
            + mlp_flops(**args) + 2 * width * n_classes)
