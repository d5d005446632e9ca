"""Plain reference of MobileNetV2, CIFAR variant, and of its first
pipelined SGD steps.

Written from the published description (Sandler et al., arXiv:1801.04381,
Table 2: width 1.0, 17 inverted-residual blocks between a stem and a
1x1-conv head), with the CIFAR changes of the paper this repository
reproduces: a 3x3 stride-1 stem and the first stride-2 block de-strided.
BatchNorm normalises with the batch's own statistics (training mode).

Nothing here imports the system under test. The weights and the batches
are drawn from the seed with the same recipe the system states for its
workload (``jax.random.split`` of ``PRNGKey(seed)`` into 64 keys drawn in
layer order; class templates plus Gaussian noise from
``numpy.random.default_rng(seed)``), so that both sides start from the
same numbers without the reference reading any of the system's.

Precisions: ``"f32"`` computes in float32 with every convolution and
matrix product at ``Precision.HIGHEST`` (the reference);
``"f32_default"`` is float32 at the TPU's default precision, where
convolutions and matrix products take bf16 operands: the precision the
configurations state. ``"fp8"`` rounds those operands to float8 (e4m3)
instead, the step below bf16 (the lower-precision control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# (expansion t, out channels c, repeats n, stride s), CIFAR strides
IR_SPEC = [(1, 16, 1, 1), (6, 24, 2, 1), (6, 32, 3, 2), (6, 64, 4, 2),
           (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]
STEM_CH, HEAD_CH, NUM_CLASSES = 32, 1280, 10
NUM_LAYERS = 2 + sum(n for _, _, n, _ in IR_SPEC)

# The WorkloadSpec fields a configuration of this model sets; every
# function below takes them by keyword and ignores the others.
SPEC_KEYS = ("image_hw", "noise")
# A cell cut to a size the CPU runs in seconds: 8 images of 16x16 and the
# program's analytic profile in place of timing every layer. (At 8x8 the
# last blocks normalise over 8 values, and rounding grows over the
# compared steps past the limits.)
CPU_CUT = {"batch": 8, "image_hw": 16, "profile": "mobilenetv2"}


def layer_meta() -> list[dict]:
    meta = [{"kind": "stem", "cin": 3, "cout": STEM_CH, "stride": 1, "t": 1}]
    cin = STEM_CH
    for t, c, n, s in IR_SPEC:
        for i in range(n):
            meta.append({"kind": "ir", "cin": cin, "cout": c,
                         "stride": s if i == 0 else 1, "t": t})
            cin = c
    meta.append({"kind": "head", "cin": cin, "cout": NUM_CLASSES,
                 "stride": 1, "t": 1})
    return meta


# ------------------------------ weights ------------------------------

def _conv_w(key, kh, kw, cin, cout, groups=1):
    fan = kh * kw * cin // groups
    return jax.random.normal(key, (kh, kw, cin // groups, cout)) / np.sqrt(fan)


def _bn(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


@jax.jit
def _init_from_key(key):
    ks = iter(jax.random.split(key, 64))
    layers = []
    for m in layer_meta():
        if m["kind"] == "stem":
            layers.append({"w": _conv_w(next(ks), 3, 3, 3, m["cout"]),
                           "bn": _bn(m["cout"])})
        elif m["kind"] == "ir":
            hid = m["cin"] * m["t"]
            p = {"bn1": _bn(hid), "bn2": _bn(hid), "bn3": _bn(m["cout"]),
                 "w_dw": _conv_w(next(ks), 3, 3, hid, hid, groups=hid),
                 "w_proj": _conv_w(next(ks), 1, 1, hid, m["cout"])}
            if m["t"] != 1:
                p["w_exp"] = _conv_w(next(ks), 1, 1, m["cin"], hid)
            layers.append(p)
        else:
            layers.append({"w": _conv_w(next(ks), 1, 1, m["cin"], HEAD_CH),
                           "bn": _bn(HEAD_CH),
                           "fc_w": jax.random.normal(
                               next(ks), (HEAD_CH, m["cout"])) * 0.01,
                           "fc_b": jnp.zeros((m["cout"],))})
    return layers


def init_params(seed: int, **_) -> list:
    """Per-layer parameter pytrees, float32, made on the device in one
    jitted call."""
    return _init_from_key(jax.random.PRNGKey(seed))


def make_batches(seed: int, count: int, batch: int, *, image_hw: int,
                 noise: float, **_) -> list[dict]:
    """``count`` labelled batches: a random template per class plus
    Gaussian noise, NHWC float32."""
    rng = np.random.default_rng(seed)
    templates = rng.normal(
        0, 1, (NUM_CLASSES, image_hw, image_hw, 3)).astype(np.float32)
    out = []
    for _ in range(count):
        labels = rng.integers(0, NUM_CLASSES, batch)
        x = templates[labels] + noise * rng.normal(
            0, 1, (batch, image_hw, image_hw, 3)).astype(np.float32)
        out.append({"x": x.astype(np.float32),
                    "labels": labels.astype(np.int32)})
    return out


# ------------------------------ forward ------------------------------

def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def _conv(x, w, stride, groups, precision):
    if precision == "fp8":
        x, w, precision = _fp8(x), _fp8(w), jax.lax.Precision.DEFAULT
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=precision)


def _batchnorm(p, x, eps=1e-5):
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _layer(p, m, x, precision):
    relu6 = jax.nn.relu6
    if m["kind"] == "stem":
        return relu6(_batchnorm(p["bn"], _conv(x, p["w"], m["stride"], 1,
                                               precision)))
    if m["kind"] == "ir":
        h = x
        if m["t"] != 1:
            h = relu6(_batchnorm(p["bn1"], _conv(h, p["w_exp"], 1, 1,
                                                 precision)))
        h = relu6(_batchnorm(p["bn2"], _conv(h, p["w_dw"], m["stride"],
                                             h.shape[-1], precision)))
        h = _batchnorm(p["bn3"], _conv(h, p["w_proj"], 1, 1, precision))
        if m["stride"] == 1 and m["cin"] == m["cout"]:
            h = h + x
        return h
    h = relu6(_batchnorm(p["bn"], _conv(x, p["w"], 1, 1, precision)))
    h = jnp.mean(h, axis=(1, 2))
    w = p["fc_w"]
    if precision == "fp8":
        h, w, precision = _fp8(h), _fp8(w), jax.lax.Precision.DEFAULT
    return jnp.dot(h, w, precision=precision) + p["fc_b"]


PRECISIONS = {"f32": jax.lax.Precision.HIGHEST,
              "f32_default": jax.lax.Precision.DEFAULT, "fp8": "fp8"}


def loss(params, x, labels, precision_name: str = "f32",
         zero_after: tuple = ()):
    """Mean cross-entropy of the whole model on one batch. ``zero_after``
    names layers whose output is replaced by zeros (a planted fault: the
    exchange between pipeline stages left out)."""
    if precision_name not in PRECISIONS:
        raise ValueError(f"unknown precision {precision_name!r}")
    precision = PRECISIONS[precision_name]
    h = x
    for j, (p, m) in enumerate(zip(params, layer_meta())):
        h = _layer(p, m, h, precision)
        if j in zero_after:
            h = jnp.zeros_like(h)
    logp = jax.nn.log_softmax(h)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)
    return jnp.mean(nll)


_loss_and_grad = jax.jit(jax.value_and_grad(loss), static_argnums=(3, 4))


def version_for_batch(b: int, n_stages: int) -> int:
    """Weights a batch is forwarded and backwarded with in an n-stage 1F1B
    pipeline with weight stashing and vertical sync (PipeDream; the paper's
    Sec. III-C): the version after ``b - n + 1`` updates."""
    return max(0, b - n_stages + 1)


def first_steps(params, batches, *, lr: float, n_stages: int, steps: int,
                precision_name: str = "f32", zero_after: tuple = ()) -> dict:
    """Follow the pipelined plain-SGD recurrence for ``steps`` batches:
    ``P[k+1] = P[k] - lr * grad L_k(P[v(k)])`` with ``loss_k = L_k(P[v(k)])``.
    Returns the losses, the first gradient and every version's weights."""
    versions = [params]
    losses, grads = [], []
    for k in range(steps):
        b = batches[k]
        val, g = _loss_and_grad(versions[version_for_batch(k, n_stages)],
                                jnp.asarray(b["x"]), jnp.asarray(b["labels"]),
                                precision_name, tuple(zero_after))
        losses.append(float(val))
        grads.append(g)
        versions.append(jax.tree.map(lambda p, d: p - lr * d,
                                     versions[-1], g))
    return {"losses": losses, "grad0": grads[0], "versions": versions}


# ------------------------------ layout ------------------------------

def leaf_sizes(params) -> list[list[int]]:
    """Per layer, the element count of each leaf in ``jax.tree.leaves``
    order: the order in which a layer's weights are packed flat."""
    return [[int(np.prod(a.shape)) for a in jax.tree.leaves(p)]
            for p in params]


def flat_layers(params) -> list[np.ndarray]:
    """Each layer's leaves, raveled and concatenated in leaf order."""
    return [np.concatenate([np.ravel(np.asarray(a, np.float32))
                            for a in jax.tree.leaves(p)]) for p in params]


# ------------------------------ cost ------------------------------

def forward_flops_per_sample(*, image_hw: int, **_) -> float:
    """Multiply-adds x 2 of one sample's forward pass: every convolution
    and the classifier. A t = 1 block has no expansion convolution."""
    total, hw = 0.0, image_hw
    for m in layer_meta():
        if m["kind"] == "stem":
            hw = hw // m["stride"]
            total += 2 * 9 * m["cin"] * m["cout"] * hw * hw
        elif m["kind"] == "ir":
            hid = m["cin"] * m["t"]
            hw_out = hw // m["stride"]
            if m["t"] != 1:
                total += 2 * hw * hw * m["cin"] * hid
            total += 2 * 9 * hid * hw_out * hw_out
            total += 2 * hw_out * hw_out * hid * m["cout"]
            hw = hw_out
        else:
            total += 2 * hw * hw * m["cin"] * HEAD_CH
            total += 2 * HEAD_CH * m["cout"]
    return float(total)


def param_count(**_) -> int:
    """Parameters of the whole model, from the shapes alone."""
    shapes = jax.eval_shape(_init_from_key, jax.random.PRNGKey(0))
    return int(sum(np.prod(a.shape) for a in jax.tree.leaves(shapes)))
