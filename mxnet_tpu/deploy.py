"""``mx.deploy`` — StableHLO model export / import.

Reference deployment surface: the C predict API
(include/mxnet/c_predict_api.h — load symbol.json + params, run inference
from any process) and ONNX export (python/mxnet/contrib/onnx/).

TPU-native re-design: the portable artifact is a serialized StableHLO
program (jax.export) plus a params .npz — the compiler IR *is* the exchange
format, so a fresh process (or a non-Python XLA runtime: C++ PjRt, IFRT
serving) can reload and execute without the framework, which is exactly the
role c_predict_api.cc plays for the reference.  Versioned serialization and
cross-platform lowering come from jax.export's calling convention.

Artifact layout for ``export_model(prefix)``:
  {prefix}-model.stablehlo   serialized StableHLO with embedded vjp-free
                             inference function (params are arguments)
  {prefix}-params.npz        parameter arrays in call order
  {prefix}-meta.json         input/output signature + param names

Format history (``meta["format_version"]``):
  v1  input signature + param names only; batch dim traced FIXED at the
      example input's shape.
  v2  adds ``output_shape``/``output_dtype`` and ``dynamic_batch``: the
      program is exported with a SYMBOLIC leading batch dim (jax.export
      shape polymorphism) whenever the model permits, so one artifact
      serves every request size — the enabler for ``mx.serving``'s
      bucketed continuous batching.  v1 artifacts still load (the missing
      fields default to fixed-batch semantics).
  v3  QUANTIZED artifacts (written by ``mx.quantization.export_quantized``
      only; fp32 exports stay v2): the program is int8-recolored
      (int8 dot_general/conv with int32 accumulation), the params .npz
      holds REAL int8 weight payloads plus ``<name>::scale`` per-channel
      scales, and meta.json carries ``quantized: true`` + the calibration
      manifest.  v1/v2 artifacts keep loading unchanged; a v3 artifact
      REFUSES the fp32 load path (``load_model(prefix)``) with a clear
      error — load it with ``load_model(prefix, quantized=True)`` /
      ``serving.Server.register(..., quantized=True)`` so a caller can
      never serve int8 numerics believing they are fp32.
  v4  GENERATION artifacts (``export_generation``): instead of one
      one-shot program the artifact carries TWO program families for
      autoregressive decoding — a length-bucketed PREFILL
      (``{prefix}-prefill-s{S}.stablehlo`` per prompt bucket) that seeds
      a paged KV cache from whole prompts, and a single-token DECODE
      step (``{prefix}-decode-w{W}.stablehlo`` per page-table width)
      with signature ``(params, kv_pages, page_table, positions,
      token_ids)``.  The page-pool size and the batch dim stay SYMBOLIC
      so the server chooses pool capacity and decode-slot count at load
      time; meta carries ``generate: true`` + the ``kv`` page spec.
      v1–v3 artifacts keep loading unchanged; a v4 artifact REFUSES the
      one-shot load path (``load_model``) — load it with
      ``load_generator(prefix)`` / ``serving.Server.register(...,
      generate=True)`` — and ``load_generator`` refuses non-v4 artifacts
      symmetrically.
  v5  SAMPLING + int8-KV generation artifacts (``export_generation``
      with ``sampling=True``, ``kv_quantized=True`` or a concrete
      ``decode_batch``; plain calls keep writing v4): every program
      takes per-row sampling controls — ``temperature`` [B] f32 (0 =
      greedy, the default), ``top_k`` [B] i32 (0 = off), ``top_p`` [B]
      f32 (1 = off) and a raw uint32 ``[B, 2]`` PRNG key folded with the
      sampled position — and the KV pool rides as ONE pytree argument,
      int8 payload + per-row f32 scale pools when ``kv_quantized``
      (HALF the HBM per cached token; drift bounded by the
      ``quant.error_budget`` knob, not the bitwise oracle).  A concrete
      ``decode_batch`` pins the decode batch dim so the Pallas
      paged-attention kernel (mx.kernels routing) can bake into the
      decode programs: its grid walks the batch.  The pool's page count
      stays symbolic there too (Pallas' dynamic-shape export) — the
      routing verdict per width lands in ``meta["paged"]`` at export,
      since an AOT artifact can never re-route at serve time.  v4
      artifacts keep loading through the same ``load_generator`` with
      greedy-only semantics.  The pool keeps a page as ``[page_size,
      heads*head_dim]`` rows (``meta["kv"]["row_width"]``); an artifact
      of a build whose pages were ``[page_size, heads, head_dim]`` is
      refused at load, in words.  ``meta["kv"]`` describes the cache in
      typed regions: the PAGES (``num_layers`` counts the layers that
      attend, ``num_heads`` the K/V heads) and, for a model that keeps
      one (``models.HybridLM``), a STATE region — ``meta["kv"]["state"]``,
      per decode slot arrays such as a recurrent state — that rides the
      same cache pytree behind the pools; such an artifact's prefill
      programs take the slot to leave the prompt's state in, its batch
      dims are concrete, and ``meta["decode_stats"]`` names the counts a
      decode step returns behind its tokens.  The PAGES region is K and V
      pools unless the model names its own (``meta["kv"]["pools"]``): a
      model of latent attention keeps ONE pool, ``kv``, whose row is a
      token's latent beside its rotary key and whose pages hold their
      tokens on the lanes (``meta["kv"]["page_layout"] == "lanes"``: a
      page is ``[row_width, page_size]``); :func:`kv_pool_names` and
      ``_kv_pool_specs`` build the cache from that description, and the
      decode route recorded in ``meta["paged"]`` is then the latent
      kernel's (``impl`` "latent").
"""
from __future__ import annotations

import json
import math as _math
import os

import numpy as _np

__all__ = ["export_model", "load_model", "StableHLOPredictor",
           "export_generation", "load_generator", "GenerationPredictor",
           "kv_pool_names", "FORMAT_VERSION", "GENERATE_FORMAT_VERSION",
           "SAMPLING_FORMAT_VERSION"]

FORMAT_VERSION = 2

#: format version stamped by ``mx.quantization.export_quantized``
QUANTIZED_FORMAT_VERSION = 3

#: format version stamped by ``export_generation`` (prefill + decode-step
#: program pair over a paged KV cache)
GENERATE_FORMAT_VERSION = 4

#: format version stamped by ``export_generation`` when sampling, int8 KV
#: pages or a concrete decode batch are requested
SAMPLING_FORMAT_VERSION = 5

#: newest format this build can load; future versions error clearly
#: instead of misinterpreting fields
MAX_SUPPORTED_FORMAT = 5


def _load_params(path, meta):
    """``{name: host array}`` from a params .npz.  numpy has no bfloat16
    of its own: ``savez`` keeps such an array as raw 2-byte records, so
    the loader views the bytes back as the dtype the meta recorded."""
    import jax.numpy as jnp
    loaded = _np.load(path)
    names = meta["param_names"]
    dtypes = meta.get("param_dtypes") or [None] * len(names)
    out = {}
    for n, dt in zip(names, dtypes):
        a = loaded[n]
        out[n] = a.view(jnp.dtype(dt)) if dt and a.dtype.kind == "V" else a
    return out


def _shape_signature(aval):
    """JSON-safe shape: symbolic dims (batch polymorphism) become None."""
    out = []
    for d in aval.shape:
        try:
            out.append(int(d))
        except Exception:  # noqa: BLE001 — symbolic dim (no constant value)
            out.append(None)
    return out


def export_model(block, prefix, example_input, include_params=True,
                 dynamic_batch=True):
    """Serialize a Gluon block's inference function to StableHLO.

    The exported program is a pure function ``f(params..., data)`` traced at
    the example input's shape/dtype; parameters ship alongside in an .npz.
    With ``dynamic_batch`` (default) the leading data dim is exported as a
    SYMBOLIC dimension so the artifact accepts any batch size — models whose
    lowering constrains the batch dim (batch-dependent reshapes) fall back
    to the fixed-shape v1 tracing semantics, recorded as
    ``meta["dynamic_batch"] = false``.  Returns the list of written paths.
    """
    import jax
    from jax import export as jexport
    import jax.numpy as jnp
    from .parallel.functional import functionalize
    from .ndarray.ndarray import NDArray

    data = example_input._data if isinstance(example_input, NDArray) \
        else jnp.asarray(example_input)

    # resolve deferred shapes with one eager forward
    from .ndarray.ndarray import _wrap
    block(_wrap(data))
    fn = functionalize(block)
    names = list(fn.params)
    values = [jnp.asarray(v) for v in fn.init_values().values()]

    def infer(params, x):
        param_map = dict(zip(names, params))
        # fixed key: inference draws nothing (training=False), and pulling
        # the global eager RNG inside jax.export tracing would leak a
        # tracer into the host-side key state
        (out,), _ = fn.apply(param_map, (x,), key=jax.random.PRNGKey(0),
                             training=False)
        return out

    jitted = jax.jit(infer)
    param_spec = tuple(jax.ShapeDtypeStruct(v.shape, v.dtype)
                       for v in values)
    exp = None
    exported_dynamic = False
    if dynamic_batch and len(data.shape) >= 1:
        try:
            b = jexport.symbolic_shape("b")[0]
            spec = (param_spec,
                    jax.ShapeDtypeStruct((b,) + tuple(data.shape[1:]),
                                         data.dtype))
            exp = jexport.export(jitted)(*spec)
            exported_dynamic = True
        except Exception:  # noqa: BLE001 — model constrains the batch dim
            exp = None
    if exp is None:
        spec = (param_spec, jax.ShapeDtypeStruct(data.shape, data.dtype))
        exp = jexport.export(jitted)(*spec)
    out_aval = exp.out_avals[0]
    paths = []
    hlo_path = prefix + "-model.stablehlo"
    with open(hlo_path, "wb") as f:
        f.write(exp.serialize())
    paths.append(hlo_path)
    meta = {
        "param_names": names,
        "param_dtypes": [str(v.dtype) for v in values],
        "input_shape": list(data.shape),
        "input_dtype": str(data.dtype),
        "output_shape": _shape_signature(out_aval),
        "output_dtype": str(out_aval.dtype),
        "dynamic_batch": exported_dynamic,
        "format_version": FORMAT_VERSION,
    }
    meta_path = prefix + "-meta.json"
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    paths.append(meta_path)
    if include_params:
        params_path = prefix + "-params.npz"
        _np.savez(params_path,
                  **{n: _np.asarray(v) for n, v in zip(names, values)})
        paths.append(params_path)
    return paths


class StableHLOPredictor:
    """Reloaded inference program (the MXPredCreate/MXPredForward analog:
    include/mxnet/c_predict_api.h).

    Parameters are staged DEVICE-RESIDENT once at construction (through
    ``io.ensure_staged``, so the one-time upload is visible on the
    ``io.h2d_sync`` counters) and reused by every ``predict`` — per-call
    param re-upload was the PR-5-era bug this fixes.  The call itself goes
    through one cached ``jax.jit`` wrapper, so repeated predicts at the
    same request shape replay a compiled program instead of re-tracing.
    """

    def __init__(self, prefix, quantized=False):
        import jax
        from jax import export as jexport
        from . import io as _io
        # meta first: the version/flavor gates must fire with a CLEAR
        # error before any program file is touched (a v4 generation
        # artifact has no -model.stablehlo at all)
        with open(prefix + "-meta.json") as f:
            self.meta = json.load(f)
        self.format_version = int(self.meta.get("format_version", 1))
        if self.format_version > MAX_SUPPORTED_FORMAT:
            raise ValueError(
                "artifact %r is deploy format v%d, newer than this "
                "build's v%d — upgrade before loading"
                % (prefix, self.format_version, MAX_SUPPORTED_FORMAT))
        if self.meta.get("generate", False):
            raise ValueError(
                "artifact %r is a GENERATION (format v%d) export: it "
                "carries prefill + decode-step programs over a paged KV "
                "cache, not a one-shot predict program. Load it with "
                "deploy.load_generator(prefix) or "
                "serving.Server.register(..., generate=True)."
                % (prefix, self.format_version))
        with open(prefix + "-model.stablehlo", "rb") as f:
            self._exported = jexport.deserialize(f.read())
        self.quantized = bool(self.meta.get("quantized", False))
        if self.quantized and not quantized:
            raise ValueError(
                "artifact %r is a QUANTIZED (format v%d) program: its "
                "params are int8 payloads and its outputs carry int8 "
                "numerics — the fp32 load path refuses it rather than "
                "silently dequantizing. Load it explicitly with "
                "deploy.load_model(prefix, quantized=True) or "
                "serving.Server.register(..., quantized=True)."
                % (prefix, self.format_version))
        if quantized and not self.quantized:
            raise ValueError(
                "artifact %r was loaded with quantized=True but is a "
                "plain fp32 export (format v%d, no quantized params); "
                "export it with mx.quantization.export_quantized or drop "
                "the flag" % (prefix, self.format_version))
        self.dynamic_batch = bool(self.meta.get("dynamic_batch", False))
        params_path = prefix + "-params.npz"
        self._params = None
        if os.path.exists(params_path):
            loaded = _load_params(params_path, self.meta)
            # one-time H2D: params live on device for the predictor's life
            self._params = tuple(
                _io.ensure_staged(loaded[n], source="deploy")
                for n in self.meta["param_names"])
        exported = self._exported
        self._call = jax.jit(lambda ps, x: exported.call(ps, x))

    def _validate_input(self, x):
        """Shape/dtype check against the exported signature — a clear
        ValueError instead of an XLA shape-mismatch stack."""
        want_shape = self.meta.get("input_shape")
        want_dtype = self.meta.get("input_dtype")
        if want_shape is None:
            return
        got = tuple(int(s) for s in x.shape)
        want = tuple(want_shape)
        if len(got) != len(want):
            raise ValueError(
                "input rank mismatch: exported signature is %s (%d dims), "
                "got shape %s" % (self.signature(), len(want), got))
        trailing_ok = got[1:] == want[1:]
        batch_ok = self.dynamic_batch or got[0] == want[0]
        if not (trailing_ok and batch_ok):
            raise ValueError(
                "input shape %s does not match the exported signature %s"
                % (got, self.signature()))
        if want_dtype is not None and str(x.dtype) != want_dtype:
            raise ValueError(
                "input dtype %s does not match the exported dtype %s"
                % (x.dtype, want_dtype))

    def signature(self):
        """Human-readable input signature, e.g. ``(N, 3, 224, 224)`` for a
        dynamic-batch artifact or ``(8, 3, 224, 224)`` for a fixed one."""
        shape = self.meta.get("input_shape") or ()
        dims = ["N" if self.dynamic_batch and i == 0 else str(d)
                for i, d in enumerate(shape)]
        return "(" + ", ".join(dims) + ")"

    def predict(self, data, params=None):
        """Run inference; returns a host numpy array."""
        import jax.numpy as jnp
        from .ndarray.ndarray import NDArray
        # validate BEFORE jnp.asarray: the backend would silently downcast
        # a float64 host array to float32, hiding the dtype mismatch
        raw = data._data if isinstance(data, NDArray) else _np.asarray(data)
        self._validate_input(raw)
        x = raw if isinstance(data, NDArray) else jnp.asarray(raw)
        if params is not None:
            ps = tuple(jnp.asarray(p) for p in params)
        else:
            ps = self._params
        if ps is None:
            raise ValueError("no params: artifact exported with "
                             "include_params=False and none were given")
        out = self._call(ps, x)
        return _np.asarray(out)

    def forward(self, data):
        return self.predict(data)


def load_model(prefix, quantized=False):
    """Reload an exported artifact.  ``quantized=True`` is REQUIRED for
    v3 quantized artifacts (and rejected for fp32 ones) — the flag is the
    caller's acknowledgement that outputs carry int8 numerics."""
    return StableHLOPredictor(prefix, quantized=quantized)


# --------------------------------------------------------- generation (v4)

def _flatten_params(tree, prefix=""):
    """Nested param dict -> sorted [(\"a/b/c\", leaf)] — the canonical
    order for the v4 .npz and meta param_names."""
    out = []
    for k in sorted(tree):
        v = tree[k]
        key = prefix + str(k)
        if isinstance(v, dict):
            out.extend(_flatten_params(v, key + "/"))
        else:
            out.append((key, v))
    return out


def _unflatten_params(flat):
    tree = {}
    for key, v in flat.items():
        parts = key.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return tree


def _pow2_family(cap):
    """Powers of two up to (and always including) ``cap``."""
    sizes, b = [], 1
    while b < cap:
        sizes.append(b)
        b *= 2
    sizes.append(int(cap))
    return tuple(sizes)


#: canonical pool-array order of a v5 KV pytree (quantized adds scales)
_KV_KEYS = ("k", "v")
_KV_KEYS_QUANT = ("k", "v", "k_scale", "v_scale")


def kv_pool_names(kv):
    """The names of the cache arrays a ``kv_spec()`` / ``meta["kv"]`` dict
    describes, in the order every program takes and returns them: its page
    pools (``kv["pools"]``; K and V where the description names none, with
    their scale pools where it is quantized), then its state region's."""
    pools = tuple(kv.get("pools", _KV_KEYS))
    if kv.get("quantized"):
        pools = _KV_KEYS_QUANT
    return pools + tuple(st["name"] for st in kv.get("state", ()))


def _kv_pool_specs(kv, num_pages, slots=None):
    """ShapeDtypeStructs of the cache a ``meta["kv"]`` dict describes, in
    :func:`kv_pool_names` order.
    Its PAGES region: one pool a name in ``kv["pools"]`` (K and V where
    there is none), ``[L, num_pages, page_size, row_width]`` over the ``L``
    layers that attend (a page is one lane-exact, contiguous block on the
    device; ``row_width`` is K/V heads x head size) or, where
    ``kv["page_layout"]`` is ``"lanes"``, ``[L, num_pages, row_width,
    page_size]`` (a page's tokens on the lanes: latent pages, whose row
    width is no multiple of the lanes), plus the ``[L, num_pages,
    page_size, H]`` f32 scale pools of an int8 pool.  Then its STATE
    region, if the model keeps one (``kv["state"]``: ``{"name", "shape",
    "dtype"}`` each): one ``[slots, *shape]`` array apiece, a row per
    decode slot.  ``num_pages`` and ``slots`` may be symbolic
    dimensions."""
    import jax
    import jax.numpy as jnp
    rows = (kv["num_layers"], num_pages, kv["page_size"])
    wide = rows + (kv["row_width"],)
    if kv.get("page_layout") == "lanes":
        wide = rows[:2] + (kv["row_width"], kv["page_size"])
    if kv.get("quantized"):
        return (jax.ShapeDtypeStruct(wide, jnp.int8),
                jax.ShapeDtypeStruct(wide, jnp.int8),
                jax.ShapeDtypeStruct(rows + (kv["num_heads"],), jnp.float32),
                jax.ShapeDtypeStruct(rows + (kv["num_heads"],), jnp.float32))
    dt = jnp.dtype(kv["dtype"])
    return tuple(jax.ShapeDtypeStruct(wide, dt)
                 for _ in kv.get("pools", _KV_KEYS)) \
        + tuple(jax.ShapeDtypeStruct((slots,) + tuple(st["shape"]),
                                     jnp.dtype(st["dtype"]))
                for st in kv.get("state", ()))


def export_generation(model, params, prefix, page_size=None,
                      max_context=None, prompt_buckets=None,
                      include_params=True, sampling=False,
                      kv_quantized=False, decode_batch=None,
                      decode_widths=None, replay=False):
    """Serialize a generation-capable model (``models.TransformerLM``,
    ``models.HybridLM``) to
    a v4/v5 artifact: one PREFILL program per prompt-length bucket and
    one single-token DECODE-step program per page-table width, both over
    a block-paged KV cache whose pool size — and the batch dim — stay
    SYMBOLIC (jax.export shape polymorphism), so the serving side picks
    pool capacity and decode-slot count without re-exporting.

    ``page_size`` defaults to the ``serving.kv_page_size`` knob and is
    BAKED into the programs (page/slot arithmetic); ``max_context``
    (default ``model.cfg.max_len``) bounds prompt + generated tokens and
    sizes the width family; ``prompt_buckets`` defaults to the pow2
    family over ``max_context`` with sub-8 buckets dropped.

    Any of the three v5 features flips the format to v5 (the plain call
    keeps writing v4 byte-identically): ``sampling`` threads per-row
    temperature / top-k / top-p / PRNG-key controls through every
    program (v5 programs ALWAYS carry them — greedy is per-row
    ``temperature=0``, the default); ``kv_quantized`` makes the pool
    int8 payload + per-row f32 scale pools (half the HBM per token);
    ``decode_batch`` pins the decode programs' batch dim to a CONCRETE
    size so trace-time kernel routing (``mx.kernels.paged_attention``)
    can bake the Pallas paged kernel in.  That is all it bakes: the
    kernel takes each layer's page pool whole, and the pool's page count
    stays symbolic inside it (the decode programs are exported under
    Pallas' dynamic-shape lowering), so ``serving.kv_pages`` remains the
    server's choice.  A decode program that cannot take the kernel —
    symbolic batch, tier off, a jax without that lowering — runs the XLA
    twin, and either way the per-width verdict and its reason are
    recorded in ``meta["paged"]``.  ``decode_widths`` keeps a subset
    of the page-table widths (the widest always): on the kernel's route a
    row costs what it holds, not the table's width, so one program may do.

    A model whose ``kv_spec()`` has a ``state`` region (per-slot arrays
    beside the pages: a recurrent state) is written as v5 whatever the
    flags: its programs take and return pages and state as the one
    cache pytree, a prefill program also takes ``slots`` [B] int32 —
    the state row each prompt's final state is left in — and a decode
    program's row b is slot b.  Where the model counts things in a decode
    step (``model.decode_stats``), the int32 counts ride behind the
    tokens in the one array a step returns (``meta["decode_stats"]``).
    A model none of whose layers attends (``kv_spec()["num_layers"] ==
    0``: all of its cache is state) gets ONE decode program, at a
    one-column page table that names no page, and no paged route is
    recorded for it; the pools it is handed have no layer and no byte.
    A model that says ``symbolic_batch = False`` (its grouped products
    run over token rows, and the shape refinement of a reloaded artifact
    cannot carry a symbolic row count through them) gets concrete batch
    dims: ``decode_batch`` is then required, and the prefill programs are
    written at the batch of one the engine runs them at
    (``meta["prefill_batch"]``).  ``replay`` (a model that says ``replay =
    True``) makes every program also say what replaying a request
    elsewhere needs — the log-probability of each token it produced and
    the experts each token it was fed chose in each mixture-of-experts
    block — behind the tokens and the counts in the same int32 array (the
    float32 bits as they are; ``meta["replay"]``: ``{"layers",
    "top_k"}``): what ``submit_generate(return_replay=True)`` is answered
    from.  Returns the list of written paths."""
    import jax
    from jax import export as jexport, lax
    import jax.numpy as jnp
    from . import config as _config
    from . import kernels as _kernels

    cfg = model.cfg
    psz = int(page_size if page_size is not None
              else _config.get("serving.kv_page_size"))
    if psz < 1:
        raise ValueError("page_size must be >= 1, got %d" % psz)
    max_context = int(max_context if max_context is not None
                      else cfg.max_len)
    if max_context > cfg.max_len:
        raise ValueError(
            "max_context %d exceeds the model's positional table (%d)"
            % (max_context, cfg.max_len))
    if prompt_buckets is None:
        fam = _pow2_family(max_context)
        prompt_buckets = tuple(s for s in fam if s >= min(8, max_context))
    prompt_buckets = tuple(sorted(int(s) for s in prompt_buckets))
    if not prompt_buckets or prompt_buckets[-1] > max_context:
        raise ValueError(
            "prompt_buckets %r must be non-empty and fit max_context %d"
            % (prompt_buckets, max_context))
    spec = model.kv_spec(quantized=True) if kv_quantized \
        else model.kv_spec()
    # a model none of whose layers attends keeps no page: its decode
    # programs take a one-column table that names none, so the page-table
    # width says nothing and one program serves every length
    paged = spec["num_layers"] > 0
    widths = _pow2_family(_math.ceil(max_context / psz)) if paged else (1,)
    if decode_widths is not None:
        keep = {int(w) for w in decode_widths} | {widths[-1]}
        if keep - set(widths):
            raise ValueError("decode_widths %r are not all of the family "
                             "%r" % (sorted(keep), widths))
        widths = tuple(w for w in widths if w in keep)
    state_names = tuple(st["name"] for st in spec.get("state", ()))
    stat_names = tuple(getattr(model, "decode_stats", ()))
    v5 = bool(sampling or kv_quantized or decode_batch is not None
              or state_names or "pools" in spec)
    if decode_batch is not None:
        decode_batch = int(decode_batch)
        if decode_batch < 1:
            raise ValueError("decode_batch must be >= 1, got %d"
                             % decode_batch)
    kv_keys = kv_pool_names(spec)
    if replay and not getattr(model, "replay", False):
        raise ValueError("%s's programs do not say what a replay needs"
                         % type(model).__name__)
    replay_kw = {"return_replay": True} if replay else {}
    routed_shape = []       # [E blocks, top_k], as the programs return it

    def _behind(nxt, more):
        # one array a call: counts, then the produced tokens' float32
        # log-probabilities (their bits), then the experts chosen
        more = list(more)
        if replay_kw:
            routed, logprob = more[-2:]
            more[-2:] = [lax.bitcast_convert_type(logprob, jnp.int32),
                         routed]
        return jnp.concatenate([nxt] + [
            m.reshape(-1).astype(nxt.dtype) for m in more]) if more else nxt

    fixed_batch = not getattr(model, "symbolic_batch", True)
    if fixed_batch and decode_batch is None:
        raise ValueError(
            "%s's programs take concrete batch dims: give decode_batch"
            % type(model).__name__)

    flat = _flatten_params(params)
    names = [n for n, _ in flat]
    # (shapes alone will do where no params file is written)
    values = [v if isinstance(v, jax.ShapeDtypeStruct) and not include_params
              else jnp.asarray(v) for _, v in flat]
    param_tree = _unflatten_params(dict(zip(names, values)))
    pspec = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), param_tree)

    paths = []
    paged_routes = {}
    grouped_routes = {}
    retention_routes = {}
    sparse_prefill_routes = {}

    def _export_one(fn, arg_specs, program, route_key=None):
        # the pool's page count stays symbolic inside the paged kernel too
        with _kernels.record_paged_routes() as routes, \
                _kernels.record_grouped_routes() as grouped, \
                _kernels.record_retention_routes() as retention, \
                _kernels.record_sparse_prefill_routes() as sparse, \
                _kernels.pallas_dynamic_shapes():
            exp = jexport.export(jax.jit(fn))(*arg_specs)
        # a program's grouped products (two an expert block) share their
        # shapes' verdict, as its retention updates (one an ``R`` block)
        # and its sparse prefills (one an ``S`` block) do; one that fell
        # back names the program
        for sites, kernel, by_program in (
                (grouped, "grouped", grouped_routes),
                (retention, "retention", retention_routes),
                (sparse, "masked", sparse_prefill_routes)):
            if sites:
                refused = [r for r in sites if r["impl"] != kernel]
                by_program[program] = dict((refused or sites)[0],
                                           sites=len(sites))
        path = "%s-%s.stablehlo" % (prefix, program)
        if route_key is not None and paged:
            # one paged_attention route per scanned stack trace; the scan
            # body compiles once, so one entry describes the whole program
            paged_routes[route_key] = (
                routes[0] if routes else {"impl": "xla",
                                          "reason": "no paged site traced",
                                          "quantized": bool(kv_quantized)})
        with open(path, "wb") as f:
            f.write(exp.serialize())
        paths.append(path)

    def _dims():
        scope = jexport.SymbolicScope()
        (b,) = jexport.symbolic_shape("b", scope=scope)
        (p,) = jexport.symbolic_shape("p", scope=scope)
        if not state_names:
            return b, p, None
        return (b, p) + jexport.symbolic_shape("s", scope=scope)

    def _kv_specs(p, slots=None):
        return _kv_pool_specs(dict(spec, page_size=psz), p, slots)

    i32 = jnp.int32

    def _sample_specs(b):
        return (jax.ShapeDtypeStruct((b,), jnp.float32),
                jax.ShapeDtypeStruct((b,), i32),
                jax.ShapeDtypeStruct((b,), jnp.float32),
                jax.ShapeDtypeStruct((b, 2), jnp.uint32))

    for s_bucket in prompt_buckets:
        w_s = _math.ceil(s_bucket / psz)
        b, p, n_slots = _dims()
        if fixed_batch:
            b = 1
        if v5:
            def prefill_fn(ps, kv, tokens, lengths, table, *rest):
                # (a model with a state region is also told its slots)
                *slots, temp, top_k, top_p, keys = rest
                sample = {"temperature": temp, "top_k": top_k,
                          "top_p": top_p, "key": keys}
                nkv, nxt, *more = model.prefill(
                    ps, dict(zip(kv_keys, kv)), tokens, lengths, table, psz,
                    sample=sample, **({"slots": slots[0]} if slots else {}),
                    **replay_kw)
                return tuple(nkv[k] for k in kv_keys), _behind(nxt, more)

            specs = (pspec, _kv_specs(p, n_slots),
                     jax.ShapeDtypeStruct((b, s_bucket), i32),
                     jax.ShapeDtypeStruct((b,), i32),
                     jax.ShapeDtypeStruct((b, w_s), i32)) \
                + ((jax.ShapeDtypeStruct((b,), i32),) if state_names
                   else ()) + _sample_specs(b)
        else:
            def prefill_fn(ps, kk, vv, tokens, lengths, table):
                kv, nxt = model.prefill(ps, {"k": kk, "v": vv}, tokens,
                                        lengths, table, psz)
                return kv["k"], kv["v"], nxt

            kks, vvs = _kv_specs(p)
            specs = (pspec, kks, vvs,
                     jax.ShapeDtypeStruct((b, s_bucket), i32),
                     jax.ShapeDtypeStruct((b,), i32),
                     jax.ShapeDtypeStruct((b, w_s), i32))
        _export_one(prefill_fn, specs, "prefill-s%d" % s_bucket)

    for width in widths:
        b, p, _ = _dims()
        bd = decode_batch if decode_batch is not None else b
        if v5:
            def decode_fn(ps, kv, token_ids, positions, table,
                          temp, top_k, top_p, keys):
                sample = {"temperature": temp, "top_k": top_k,
                          "top_p": top_p, "key": keys}
                nkv, nxt, *more = model.decode_step(
                    ps, dict(zip(kv_keys, kv)), token_ids, positions, table,
                    psz, sample=sample, **replay_kw,
                    **({"return_stats": True} if stat_names else {}))
                if replay_kw:
                    routed_shape[:] = [more[-2].shape[0], more[-2].shape[-1]]
                return tuple(nkv[k] for k in kv_keys), _behind(nxt, more)

            # a decode program's row b is state slot b
            specs = (pspec, _kv_specs(p, bd),
                     jax.ShapeDtypeStruct((bd,), i32),
                     jax.ShapeDtypeStruct((bd,), i32),
                     jax.ShapeDtypeStruct((bd, width), i32)) \
                + _sample_specs(bd)
        else:
            def decode_fn(ps, kk, vv, token_ids, positions, table):
                kv, nxt = model.decode_step(ps, {"k": kk, "v": vv},
                                            token_ids, positions, table,
                                            psz)
                return kv["k"], kv["v"], nxt

            kks, vvs = _kv_specs(p)
            specs = (pspec, kks, vvs,
                     jax.ShapeDtypeStruct((bd,), i32),
                     jax.ShapeDtypeStruct((bd,), i32),
                     jax.ShapeDtypeStruct((bd, width), i32))
        _export_one(decode_fn, specs, "decode-w%d" % width,
                    route_key=str(width))

    meta = {
        "param_names": names,
        "param_dtypes": [str(v.dtype) for v in values],
        "input_dtype": "int32",
        "format_version": (SAMPLING_FORMAT_VERSION if v5
                           else GENERATE_FORMAT_VERSION),
        "generate": True,
        "vocab_size": int(cfg.vocab_size),
        "max_context": max_context,
        "prompt_buckets": list(prompt_buckets),
        "decode_widths": list(widths),
        "kv": dict(spec, page_size=psz),
        "paged": paged_routes,
        "grouped": grouped_routes,
        "retention": retention_routes,
        "sparse_prefill": sparse_prefill_routes,
    }
    if v5:
        meta["sampling"] = True
        if decode_batch is not None:
            meta["decode_batch"] = decode_batch
        if stat_names:
            meta["decode_stats"] = list(stat_names)
        if fixed_batch:
            meta["prefill_batch"] = 1
        if replay:
            meta["replay"] = dict(zip(("layers", "top_k"), routed_shape))
    meta_path = prefix + "-meta.json"
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    paths.append(meta_path)
    if include_params:
        params_path = prefix + "-params.npz"
        _np.savez(params_path,
                  **{n: _np.asarray(v) for n, v in zip(names, values)})
        paths.append(params_path)
    return paths


class GenerationPredictor:
    """Reloaded v4 generation artifact: the prefill program family (one
    per prompt bucket), the decode-step family (one per page-table
    width), and device-resident params — the stateful-RNN
    ``c_predict_api`` analog for autoregressive serving.

    ``mx.serving`` drives the programs through its per-iteration
    scheduler; :meth:`generate` is the OFFLINE single-sequence
    convenience loop (and the shape the parity tests drive)."""

    def __init__(self, prefix, params=None):
        import jax
        from jax import export as jexport
        from . import io as _io
        with open(prefix + "-meta.json") as f:
            self.meta = json.load(f)
        self.format_version = int(self.meta.get("format_version", 1))
        if self.format_version > MAX_SUPPORTED_FORMAT:
            raise ValueError(
                "artifact %r is deploy format v%d, newer than this "
                "build's v%d — upgrade before loading"
                % (prefix, self.format_version, MAX_SUPPORTED_FORMAT))
        if not self.meta.get("generate", False):
            raise ValueError(
                "artifact %r is a one-shot predict export (format v%d, "
                "no generation programs); load it with "
                "deploy.load_model(prefix) — load_generator only accepts "
                "v4 artifacts written by deploy.export_generation"
                % (prefix, self.format_version))
        if "row_width" not in self.meta["kv"]:
            raise ValueError(
                "artifact %r keeps its K/V pages as [page_size, heads, "
                "head_dim], the layout of an older build; this build's "
                "programs take [page_size, heads*head_dim] rows — export "
                "it again with deploy.export_generation" % (prefix,))
        self.page_size = int(self.meta["kv"]["page_size"])
        self.max_context = int(self.meta["max_context"])
        self.prompt_buckets = tuple(self.meta["prompt_buckets"])
        self.decode_widths = tuple(self.meta["decode_widths"])
        self.kv_dtype = _np.dtype(self.meta["kv"]["dtype"])
        #: v5 surface — v4 artifacts default to greedy-only fp pools
        self.sampling = bool(self.meta.get("sampling", False))
        self.kv_quantized = bool(self.meta["kv"].get("quantized", False))
        db = self.meta.get("decode_batch")
        self.decode_batch = int(db) if db is not None else None
        #: per-width kernel routing verdict recorded at export (an AOT
        #: program can never re-route at serve time)
        self.paged_routes = dict(self.meta.get("paged", {}))
        #: the same for the grouped products, by program ("decode-w16",
        #: "prefill-s128"); a program without one has no entry
        self.grouped_routes = dict(self.meta.get("grouped", {}))
        #: and for the retention updates (a decode program's alone)
        self.retention_routes = dict(self.meta.get("retention", {}))
        #: and for the ``S`` blocks' attention (a prefill program's alone)
        self.sparse_prefill_routes = dict(
            self.meta.get("sparse_prefill", {}))
        self._v5 = self.format_version >= SAMPLING_FORMAT_VERSION
        #: the cache's state region (per-slot arrays beside the pages) and
        #: the names of the counts a decode step returns behind its tokens
        self.state = tuple(self.meta["kv"].get("state", ()))
        #: False for a model none of whose layers attends: the pools have
        #: no layer, a request needs no page and the tables name none
        self.paged = int(self.meta["kv"]["num_layers"]) > 0
        self.decode_stats = tuple(self.meta.get("decode_stats", ()))
        #: ``{"layers", "top_k"}`` (of the experts chosen) where every
        #: program also returns what a replay needs, else None
        self.replay = self.meta.get("replay")
        self._kv_keys = kv_pool_names(self.meta["kv"])
        self._prefill_exp = {}
        self._decode_exp = {}
        for s_bucket in self.prompt_buckets:
            with open("%s-prefill-s%d.stablehlo"
                      % (prefix, s_bucket), "rb") as f:
                self._prefill_exp[s_bucket] = jexport.deserialize(f.read())
        for width in self.decode_widths:
            with open("%s-decode-w%d.stablehlo"
                      % (prefix, width), "rb") as f:
                self._decode_exp[width] = jexport.deserialize(f.read())
        params_path = prefix + "-params.npz"
        self._params = None
        if params is not None:
            # the exporting process hands over what it exported: arrays
            # already on the device take no trip through a file
            given = dict(_flatten_params(params))
            want = list(zip(self.meta["param_names"],
                            self.meta["param_dtypes"]))
            if sorted(given) != sorted(self.meta["param_names"]) or any(
                    str(given[n].dtype) != d for n, d in want):
                raise ValueError(
                    "artifact %r: the params given are not the ones it "
                    "was exported with (names or dtypes differ)" % (prefix,))
            self._params = _unflatten_params({
                n: _io.ensure_staged(given[n], source="deploy")
                for n, _ in want})
        elif os.path.exists(params_path):
            loaded = _load_params(params_path, self.meta)
            # one-time H2D, device-resident for the predictor's life
            self._params = _unflatten_params({
                n: _io.ensure_staged(loaded[n], source="deploy")
                for n in self.meta["param_names"]})
        self._jax = jax
        self._prefill_call = {}
        self._decode_call = {}

    # program handles ------------------------------------------------
    def prefill_bucket(self, prompt_len):
        """Smallest exported prompt bucket that fits, or a clear error."""
        from . import io as _io
        s_bucket = _io.pick_bucket(self.prompt_buckets, prompt_len)
        if s_bucket is None:
            raise ValueError(
                "prompt of %d tokens exceeds the largest exported "
                "prefill bucket (%d); re-export with bigger "
                "prompt_buckets" % (prompt_len, self.prompt_buckets[-1]))
        return s_bucket

    def decode_width(self, pages_needed):
        from . import io as _io
        width = _io.pick_bucket(self.decode_widths, pages_needed)
        if width is None:
            raise ValueError(
                "sequence needs %d KV pages, more than the largest "
                "exported page-table width (%d)"
                % (pages_needed, self.decode_widths[-1]))
        return width

    def prefill_fn(self, s_bucket):
        """Cached jit wrapper for one prefill bucket, UNIFORM across
        formats: ``fn(ps, kv_tuple, tokens, lengths, table, temp, top_k,
        top_p, keys) -> (kv_tuple, next_ids)``; a model with a state
        region takes ``slots`` [B] int32 after ``table``.  The cache
        pytree is DONATED so the appended-to cache aliases in place; v4
        programs ignore the sampling args (greedy is the only lowering
        they carry)."""
        fn = self._prefill_call.get(s_bucket)
        if fn is None:
            exp = self._prefill_exp[s_bucket]
            if self._v5:
                fn = self._jax.jit(
                    lambda ps, kv, tokens, lengths, table, *rest: exp.call(
                        ps, kv, tokens, lengths, table, *rest),
                    donate_argnums=(1,))
            else:
                def fn_v4(ps, kv, tokens, lengths, table, temp, tk, tp,
                          keys):
                    kk, vv, nxt = exp.call(ps, kv[0], kv[1], tokens,
                                           lengths, table)
                    return (kk, vv), nxt
                fn = self._jax.jit(fn_v4, donate_argnums=(1,))
            self._prefill_call[s_bucket] = fn
        return fn

    def decode_fn(self, width):
        fn = self._decode_call.get(width)
        if fn is None:
            exp = self._decode_exp[width]
            if self._v5:
                fn = self._jax.jit(
                    lambda ps, kv, token_ids, positions, table, temp, tk,
                    tp, keys: exp.call(ps, kv, token_ids, positions,
                                       table, temp, tk, tp, keys),
                    donate_argnums=(1,))
            else:
                def fn_v4(ps, kv, token_ids, positions, table, temp, tk,
                          tp, keys):
                    kk, vv, nxt = exp.call(ps, kv[0], kv[1], token_ids,
                                           positions, table)
                    return (kk, vv), nxt
                fn = self._jax.jit(fn_v4, donate_argnums=(1,))
            self._decode_call[width] = fn
        return fn

    def make_kv(self, num_pages, slots=None):
        """Zeroed cache tuple sized for this artifact's KV spec —
        ``(k, v)`` or, for int8-KV artifacts, ``(k, v, k_scale,
        v_scale)`` (int8 payloads + per-row f32 scales), followed by the
        state region's ``[slots, ...]`` arrays where the model has one."""
        import jax.numpy as jnp
        return tuple(jnp.zeros(s.shape, s.dtype)
                     for s in self.kv_pool_specs(num_pages, slots))

    def kv_pool_specs(self, num_pages, slots=None):
        """ShapeDtypeStruct tuple matching :meth:`make_kv` — what the
        serving engine AOT-traces its programs against."""
        if self.state and slots is None:
            raise ValueError("this artifact's cache has a state region: "
                             "say how many decode slots it serves")
        return _kv_pool_specs(self.meta["kv"], int(num_pages),
                              None if slots is None else int(slots))

    def sample_arrays(self, temperature, top_k, top_p, seeds):
        """Host-side per-row sampling operand build: lists/arrays of
        per-row controls -> the (temp f32, top_k i32, top_p f32,
        keys uint32[B,2]) device operands every v5 program takes.  Seeds
        are 64-bit ints split across the raw uint32 key words — the
        layout ``jax.random.PRNGKey`` uses — so a request seed maps to
        ONE deterministic stream."""
        temp = _np.asarray(temperature, _np.float32).reshape(-1)
        B = temp.shape[0]
        keys = _np.zeros((B, 2), _np.uint32)
        s = _np.asarray(seeds, _np.uint64).reshape(-1)
        keys[:, 0] = (s >> _np.uint64(32)).astype(_np.uint32)
        keys[:, 1] = (s & _np.uint64(0xFFFFFFFF)).astype(_np.uint32)
        return (temp, _np.asarray(top_k, _np.int32).reshape(-1),
                _np.asarray(top_p, _np.float32).reshape(-1), keys)

    # offline convenience --------------------------------------------
    def generate(self, prompt, max_new_tokens, eos_id=None, params=None,
                 temperature=0.0, top_k=0, top_p=1.0, seed=0):
        """Decode ONE sequence through the exported programs (prefill
        into a private page pool, then single-token decode steps).
        Default is greedy; ``temperature``/``top_k``/``top_p``/``seed``
        engage v5 sampling (a ValueError on v4 artifacts, which only
        carry the greedy lowering).  Returns generated ids (eos included
        when hit) as np.int32 — the exact stream the serving scheduler
        produces for the same request, minus the batching."""
        import jax.numpy as jnp
        ps = params if params is not None else self._params
        if ps is None:
            raise ValueError("no params: artifact exported with "
                             "include_params=False and none were given")
        temperature = float(temperature)
        if temperature > 0 and not self.sampling:
            raise ValueError(
                "temperature=%g needs a sampling (format v5) artifact; "
                "this one is format v%d (greedy only) — re-export with "
                "export_generation(..., sampling=True)"
                % (temperature, self.format_version))
        prompt = _np.asarray(prompt, _np.int32).reshape(-1)
        plen = int(prompt.shape[0])
        max_new = int(max_new_tokens)
        if plen < 1 or max_new < 1:
            raise ValueError("need a non-empty prompt and "
                             "max_new_tokens >= 1")
        if plen + max_new > self.max_context:
            raise ValueError(
                "prompt (%d) + max_new_tokens (%d) exceeds max_context "
                "%d" % (plen, max_new, self.max_context))
        psz = self.page_size
        need = _math.ceil((plen + max_new) / psz) if self.paged else 0
        # a concrete decode_batch pins the decode batch dim: row 0 is
        # the live sequence (and state slot 0), the pad rows run against
        # an all-sentinel table (their writes drop, their outputs are
        # ignored)
        Bd = self.decode_batch or 1
        pool = max(need, 1)
        kv = self.make_kv(pool, Bd if self.state else None)
        slot0 = (jnp.zeros((1,), jnp.int32),) if self.state else ()
        pages = _np.arange(need, dtype=_np.int32)
        sentinel = pool
        s_bucket = self.prefill_bucket(plen)
        w_s = _math.ceil(s_bucket / psz)
        tokens = _np.zeros((1, s_bucket), _np.int32)
        tokens[0, :plen] = prompt
        table = _np.full((1, w_s), sentinel, _np.int32)
        table[0, :min(w_s, need)] = pages[:w_s]
        samp1 = self.sample_arrays([temperature], [top_k], [top_p],
                                   [int(seed)])
        kv, nxt = self.prefill_fn(s_bucket)(
            ps, kv, jnp.asarray(tokens),
            jnp.asarray([plen], jnp.int32), jnp.asarray(table), *slot0,
            *samp1)
        out = [int(nxt[0])]
        pos = plen
        sampB = self.sample_arrays(
            [temperature] + [0.0] * (Bd - 1), [int(top_k)] + [0] * (Bd - 1),
            [float(top_p)] + [1.0] * (Bd - 1), [int(seed)] + [0] * (Bd - 1))
        while len(out) < max_new and (eos_id is None
                                      or out[-1] != int(eos_id)):
            width = self.decode_width(pos // psz + 1 if self.paged else 1)
            table = _np.full((Bd, width), sentinel, _np.int32)
            table[0, :min(width, need)] = pages[:width]
            toks = _np.zeros((Bd,), _np.int32)
            toks[0] = out[-1]
            poss = _np.zeros((Bd,), _np.int32)
            poss[0] = pos
            kv, nxt = self.decode_fn(width)(
                ps, kv, jnp.asarray(toks), jnp.asarray(poss),
                jnp.asarray(table), *sampB)
            out.append(int(nxt[0]))
            pos += 1
        return _np.asarray(out, _np.int32)


def load_generator(prefix, params=None):
    """Reload a v4/v5 generation artifact (prefill + decode-step program
    families over a paged KV cache; v5 adds sampling controls, int8 KV
    pages and/or a pinned decode batch).  Refuses one-shot v1–v3
    artifacts — those load with :func:`load_model`.  ``params`` (the
    pytree the artifact was exported with) takes the place of the
    ``-params.npz`` file, which an ``include_params=False`` export lacks."""
    return GenerationPredictor(prefix, params=params)
