"""ResNet v1 with bottleneck blocks (He et al., arXiv:1512.03385, Table 1)
in plain ``jax.numpy`` / ``lax.conv_general_dilated``, float32, matmul
precision "highest": forward, softmax cross-entropy, ``jax.grad`` of it, and
SGD with momentum and weight decay.  It imports nothing from the program;
it is handed parameter VALUES, keyed by the Gluon model zoo's names with the
net's own prefix stripped (``conv0_weight``, ``stage1_conv0_weight``,
``stage1_batchnorm0_gamma``, ``dense0_weight`` ...).

Departures from the paper, as the configuration file lists them: the stride
of a down-sampling block sits on its first 1x1 convolution (the paper's
form; "v1.5" moved it), the block's two 1x1 convolutions carry a bias and its 3x3
does not (as the Gluon zoo's), BatchNorm uses batch statistics (biased variance, eps
1e-5)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5


def _conv(x, w, stride, pad, b=None):
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)
    return y if b is None else y + b[None, :, None, None]


def _bn(x, p, name):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    g = p[name + "_gamma"][None, :, None, None]
    b = p[name + "_beta"][None, :, None, None]
    return (x - mean) * lax.rsqrt(var + EPS) * g + b


def forward(p, x, layers):
    """x [N,3,H,W] float32 -> logits [N, classes]."""
    with jax.default_matmul_precision("highest"):
        x = _conv(x, p["conv0_weight"], 2, 3)
        x = jax.nn.relu(_bn(x, p, "batchnorm0"))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
        for s, blocks in enumerate(layers, start=1):
            st = "stage%d_" % s
            c = b = 0     # convolutions and BatchNorms number on per stage
            for i in range(blocks):
                stride = 2 if (i == 0 and s > 1) else 1
                res = x
                y = x
                for k, (cs, pad) in enumerate(((stride, 0), (1, 1), (1, 0))):
                    y = _conv(y, p["%sconv%d_weight" % (st, c)], cs, pad,
                              p.get("%sconv%d_bias" % (st, c)))
                    y = _bn(y, p, "%sbatchnorm%d" % (st, b))
                    c, b = c + 1, b + 1
                    if k < 2:
                        y = jax.nn.relu(y)
                if i == 0:    # projection shortcut: channels change
                    res = _conv(x, p["%sconv%d_weight" % (st, c)], stride, 0)
                    res = _bn(res, p, "%sbatchnorm%d" % (st, b))
                    c, b = c + 1, b + 1
                x = jax.nn.relu(y + res)
        x = jnp.mean(x, axis=(2, 3))
        return jnp.dot(x, p["dense0_weight"].T,
                       precision=lax.Precision.HIGHEST) + p["dense0_bias"]


def loss(p, x, label, layers):
    """Mean softmax cross-entropy over the batch; label [N] class ids."""
    logits = forward(p, x.astype(jnp.float32), layers)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32)[:, None], axis=1)[:, 0]
    return -jnp.mean(picked)


def is_trainable(name):
    return not (name.endswith("_running_mean")
                or name.endswith("_running_var"))


def sgd_step(p, mom, x, label, layers, lr, momentum, wd):
    """One step of SGD with momentum and weight decay on every trainable
    value: ``(loss, new values, new momentum)``."""
    train = {n: v for n, v in p.items() if is_trainable(n)}
    value, grads = jax.value_and_grad(
        lambda t: loss({**p, **t}, x, label, layers))(train)
    new_p, new_m = dict(p), {}
    for n, g in grads.items():
        g = g + wd * p[n]
        new_m[n] = momentum * mom[n] + lr * g
        new_p[n] = p[n] - new_m[n]
    return value, new_p, new_m
