"""Model FLOPs of the program's ``make_mlp`` net: a multiply-add counts 2,
forward per sample over the dense layers.  Backward is twice the
forward."""


def forward_flops(in_dim, widths=(256, 256), n_classes=10):
    dims = [in_dim, *widths, n_classes]
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
