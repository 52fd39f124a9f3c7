"""``mx.runtime`` — runtime feature introspection + program tuning.

Reference: python/mxnet/runtime.py `Features`/`feature_list` over the libinfo
build flags (include/mxnet/libinfo.h:141-193 — CUDA, CUDNN, MKLDNN,
DIST_KVSTORE...).  TPU-native: features reflect what this build can actually
do (platform backends, pallas availability, distributed init), discovered at
query time instead of baked at compile time.

Program tuning (``scan_stack``): the knob-driven scan/unroll + remat
policy applied to repeated-layer stacks — the TPU analog of the
reference graph optimizer's memory-vs-recompute planning.  Scanning the
layer stack keeps trace and compile time O(1) in depth; a
``jax.checkpoint`` policy trades activation memory for recompute in the
backward pass.
"""
from __future__ import annotations

from collections import namedtuple

__all__ = ["Feature", "Features", "feature_list", "is_enabled",
           "scan_stack", "checkpoint_policy", "cache_root",
           "configure_compile_cache"]

Feature = namedtuple("Feature", ["name", "enabled"])


def cache_root():
    """The checkout's one cache directory (git-ignored): XLA's persistent
    compile cache lives here, so nothing outside the tree decides what a
    run compiles."""
    import os
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")


_CACHE_CONFIGURED = [None]  # the directory jax's cache was last reset for


def configure_compile_cache():
    """Turn on jax's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set the place is
    the caller's choice — jax reads it by itself and no directory is set
    here; where it is not, the cache goes to :func:`cache_root`, a fixed
    path, because the path is part of the cache key and a directory that
    moves never hits.  Idempotent; entry points (chip_smoke.py, the
    tools, ``Server.start()``) call it before their first big compile.

    The key covers the program's metadata too (jax leaves it out by
    default): a cached executable carries the operation names it was
    compiled with, and those names — ``jax.named_scope`` paths, kernel
    names, source lines — are what a profile of it shows.  With a key
    blind to them, an executable cached by an older checkout comes back
    under this one's programs with the old names (or none), and
    ``mx.perf.op_names`` and every device trace read stale scopes.  The
    price is a recompile after an edit that moves traced source lines."""
    import os
    import jax
    from jax.experimental.compilation_cache import compilation_cache as _cc
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = cache_root()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if _CACHE_CONFIGURED[0] != cache_dir:
        # jax decides whether the cache is in use on the FIRST compile of
        # the process; a directory that arrives later (params staged and
        # models warmed before start()) is ignored until that is undone
        _cc.reset_cache()
        _CACHE_CONFIGURED[0] = cache_dir
    return cache_dir


def _detect():
    import jax
    feats = {}

    def have(mod):
        try:
            __import__(mod)
            return True
        except Exception:
            return False

    try:
        platforms = {d.platform for d in jax.devices()}
    except Exception:
        platforms = set()
    feats["TPU"] = "tpu" in platforms
    feats["CPU"] = True
    feats["GPU"] = "gpu" in platforms or "cuda" in platforms
    feats["PALLAS"] = have("jax.experimental.pallas")
    feats["DIST_KVSTORE"] = True          # jax.distributed-backed
    feats["INT64_TENSOR_SIZE"] = True
    feats["SIGNAL_HANDLER"] = True
    feats["OPENCV"] = False               # PIL-based image path
    feats["PIL"] = have("PIL")
    feats["BLAS_OPEN"] = False            # XLA supplies all kernels
    feats["MKLDNN"] = False
    feats["CUDA"] = False
    feats["CUDNN"] = False
    feats["NATIVE_IO"] = _native_io_available()
    return feats


def _native_io_available():
    try:
        from .native import lib as _native  # noqa: F401
        return _native.available()
    except Exception:
        return False


class Features(dict):
    """Mapping name -> Feature (reference Features mapping API)."""

    instance = None

    def __init__(self):
        super().__init__([(k, Feature(k, v)) for k, v in _detect().items()])

    def __repr__(self):
        return "[%s]" % ", ".join(
            "✔ %s" % k if v.enabled else "✖ %s" % k
            for k, v in sorted(self.items()))

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError("Feature %r does not exist" % (feature_name,))
        return self[feature_name].enabled


def feature_list():
    return list(Features().values())


def is_enabled(feature_name):
    return Features().is_enabled(feature_name)


# --------------------------------------------------------- program tuning
def checkpoint_policy(name):
    """Resolve a remat policy name to a ``jax.checkpoint`` policy:
    '' -> None (no remat), 'dots' -> save matmul results and recompute
    the elementwise rest (the MFU-friendly default — recomputing
    elementwise ops is cheap, recomputing matmuls is not), 'full' ->
    save only the layer inputs (maximum memory saving)."""
    import jax
    if name == "dots":
        pols = jax.checkpoint_policies
        return (getattr(pols, "dots_saveable", None)
                or pols.checkpoint_dots)
    if name == "full":
        return "full"
    return None


def scan_stack(body, carry, xs):
    """Run ``body(carry, x)`` over the leading axis of ``xs`` with the
    knob-selected stacking strategy.

    ``runtime.stack_mode='scan'`` (default) lowers one ``lax.scan`` —
    the program traces and compiles the layer ONCE regardless of depth,
    which is where the trace/compile-time win over an unrolled stack
    comes from.  ``'unroll'`` inlines every layer (larger programs,
    but XLA can specialize per layer).  ``runtime.remat`` wraps the body
    in ``jax.checkpoint`` with the matching policy; '' applies no wrapper
    at all so default-knob programs stay byte-identical to the
    pre-tuning lowering.

    The stack traces under the ``mx.layers`` name scope: what a device
    profile shows there and under none of the layer's own scopes is the
    stacking itself — slicing each layer's ``xs`` out and restacking its
    ``ys``.  So what is large and updated a layer at a time belongs in
    the ``carry``, whole, and is written in place at the layer's index
    (the K/V page pools of ``models.TransformerLM``'s serving programs:
    as ``xs``/``ys`` each layer's 100 MB pool was sliced out and written
    back, and the stack copied besides).
    """
    import jax
    with jax.named_scope("mx.layers"):
        return _scan_stack(body, carry, xs)


def _scan_stack(body, carry, xs):
    import jax
    from jax import lax
    from . import config as _config
    mode = _config.get("runtime.stack_mode")
    remat = _config.get("runtime.remat")
    if remat:
        policy = checkpoint_policy(remat)
        if policy == "full":
            body = jax.checkpoint(body)
        else:
            body = jax.checkpoint(body, policy=policy)
    if mode == "unroll":
        import jax.numpy as jnp
        leaves = jax.tree_util.tree_leaves(xs)
        n = leaves[0].shape[0]
        ys = []
        for i in range(n):
            x = jax.tree_util.tree_map(lambda a: a[i], xs)
            carry, y = body(carry, x)
            ys.append(y)
        if ys and ys[0] is None:
            return carry, None
        # stack per-layer outputs like lax.scan does
        return carry, jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *ys)
    return lax.scan(body, carry, xs)
