"""Plain reference of ``resnet50.json``: ResNet-50 with GroupNorm.

Straightforward ``jax.numpy``/``lax`` of the published network, written
from its description and not from the program: a 7x7/2 stem convolution,
GroupNorm and ReLU, a 3x3/2 max pool, four stages of bottleneck blocks
(1x1, 3x3, 1x1 convolutions, each followed by GroupNorm; ReLU after the
first two and after the residual sum; a projection on the first block of a
stage; stride 2 on the 3x3 of the first block of stages 2-4), global
average pooling and a dense classifier, with the mean softmax cross
entropy as the loss.

``mode`` is the precision of the arithmetic: ``"f32"`` for the reference,
``"high"`` for the control: every convolution and the classifier's matmul,
forward and backward, as three bfloat16 passes with float32 accumulation
(``a_hi b_hi + a_hi b_lo + a_lo b_hi``, what a TPU runs at matmul precision
"high"), written out so that it computes the same on any backend. The
configuration states float32 at "highest", so "high" is the step a later
change would be tempted to take.
"""
from __future__ import annotations

INPUT = "images"
REF_BLOCK_ROWS = 32  # rows of the global batch per reference block
CONTROL = "high"


def _tree(sizes: dict, leaf, gn):
    """The parameter tree, built from ``leaf(name, shape, fan_in)`` and
    ``gn(name, channels)``."""
    c0 = sizes["stem_width"]
    p = {"stem": leaf("stem", (7, 7, 3, c0), 7 * 7 * 3),
         "stem_gn": gn("stem_gn", c0)}
    cin = c0
    for si, (n, w) in enumerate(zip(sizes["blocks"], sizes["widths"])):
        mid = w // 4
        for bi in range(n):
            k = f"s{si}b{bi}"
            blk = {
                "c1": leaf(f"{k}c1", (1, 1, cin, mid), cin),
                "g1": gn(f"{k}g1", mid),
                "c2": leaf(f"{k}c2", (3, 3, mid, mid), 9 * mid),
                "g2": gn(f"{k}g2", mid),
                "c3": leaf(f"{k}c3", (1, 1, mid, w), mid),
                "g3": gn(f"{k}g3", w),
            }
            if bi == 0:
                blk["proj"] = leaf(f"{k}proj", (1, 1, cin, w), cin)
                blk["gproj"] = gn(f"{k}gproj", w)
            p[k] = blk
            cin = w
    p["head"] = leaf("head", (cin, sizes["n_classes"]), cin)
    p["head_b"] = gn("head_b", sizes["n_classes"])["b"]
    return p


def init_params(sizes: dict, key):
    """Weights from ``key``: normal with std 1/sqrt(fan_in) for every
    convolution and the classifier, GroupNorm scale 1 and bias 0."""
    import zlib

    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(sizes["param_dtype"])

    def leaf(name, shape, fan_in):
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        w = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(float(fan_in))
        return w.astype(dt)

    def gn(name, c):
        return {"s": jnp.ones((c,), dt), "b": jnp.zeros((c,), dt)}

    return _tree(sizes, leaf, gn)


def _conv(x, w, stride=1):
    from jax import lax

    return lax.conv_general_dilated(x, w, (stride, stride), "SAME",
                                    dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, g, groups, eps):
    import jax.numpy as jnp

    n, h, w, c = x.shape
    xg = x.reshape(n, h, w, groups, c // groups)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + eps)
    return xg.reshape(n, h, w, c) * g["s"] + g["b"]


def _split(a):
    """``a`` as two bfloat16 parts (held in float32): ``hi + lo ~ a``.
    Rounded by ``reduce_precision``: a TPU compiler may drop an ``astype``
    round trip through bfloat16, which would leave ``hi = a``."""
    from jax import lax

    def bf16(x):
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    hi = bf16(a)
    return hi, bf16(a - hi)


def three_pass(op):
    """The bilinear ``op(a, b)`` as three bfloat16 passes, forward and in
    both products of its backward pass."""
    import jax

    def passes(f, a, b):
        (ah, al), (bh, bl) = _split(a), _split(b)
        return f(ah, bh) + f(ah, bl) + f(al, bh)

    @jax.custom_vjp
    def high(a, b):
        return passes(op, a, b)

    def fwd(a, b):
        return passes(op, a, b), (a, b)

    def bwd(res, g):
        a, b = res
        da = passes(lambda g_, b_: jax.vjp(lambda x: op(x, b_), a)[1](g_)[0],
                    g, b)
        db = passes(lambda a_, g_: jax.vjp(lambda y: op(a_, y), b)[1](g_)[0],
                    a, g)
        return da, db

    high.defvjp(fwd, bwd)
    return high


def logits(params, images, sizes: dict, mode: str = "f32"):
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    if mode not in ("f32", "high"):
        raise ValueError(f"unknown mode {mode!r}")
    p = params
    groups, eps = sizes["groups"], sizes["gn_eps"]
    convs = {s: functools.partial(_conv, stride=s) for s in (1, 2)}
    dense = jnp.matmul
    if mode == "high":
        convs = {s: three_pass(c) for s, c in convs.items()}
        dense = three_pass(jnp.matmul)

    def conv(x, w, stride=1):
        return convs[stride](x, w)

    def gn_relu(x, g, relu=True):
        x = _group_norm(x, g, groups, eps)
        return jax.nn.relu(x) if relu else x

    x = images.astype(jnp.float32)
    x = gn_relu(conv(x, p["stem"], 2), p["stem_gn"])
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), "SAME")
    for si, n in enumerate(sizes["blocks"]):
        for bi in range(n):
            b = p[f"s{si}b{bi}"]
            stride = 2 if (bi == 0 and si > 0) else 1
            h = gn_relu(conv(x, b["c1"]), b["g1"])
            h = gn_relu(conv(h, b["c2"], stride), b["g2"])
            h = gn_relu(conv(h, b["c3"]), b["g3"], relu=False)
            if "proj" in b:
                x = gn_relu(conv(x, b["proj"], stride), b["gproj"], relu=False)
            x = jax.nn.relu(x + h)
    x = jnp.mean(x, axis=(1, 2))
    return dense(x, p["head"]) + p["head_b"]


def ref_loss(params, batch, sizes: dict, mode: str = "f32"):
    """Mean softmax cross entropy of ``batch`` (images, labels)."""
    import jax
    import jax.numpy as jnp

    z = logits(params, batch["images"], sizes, mode)
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None],
                                         axis=-1))


def conv_macs(sizes: dict) -> int:
    """Multiply-accumulates of one image's forward pass: every convolution
    and the classifier, from the shapes ("SAME" padding)."""
    img = sizes["image_size"]
    macs = 0

    def conv(hw, k, cin, cout, stride):
        out = -(-hw // stride)
        return out, out * out * k * k * cin * cout

    c0 = sizes["stem_width"]
    hw, m = conv(img, 7, 3, c0, 2)
    macs += m
    hw = -(-hw // 2)  # max pool
    cin = c0
    for si, (n, w) in enumerate(zip(sizes["blocks"], sizes["widths"])):
        mid = w // 4
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            _, m1 = conv(hw, 1, cin, mid, 1)
            hw2, m2 = conv(hw, 3, mid, mid, stride)
            _, m3 = conv(hw2, 1, mid, w, 1)
            macs += m1 + m2 + m3
            if bi == 0:
                macs += conv(hw, 1, cin, w, stride)[1]
            hw, cin = hw2, w
    return macs + cin * sizes["n_classes"]


def model_flops(sizes: dict, mix: dict) -> float:
    """Model FLOPs of one training step: forward and backward, 3 x 2 x the
    forward's multiply-accumulates, per image of the global batch."""
    return 3.0 * 2.0 * conv_macs(sizes) * mix["global_batch"]
