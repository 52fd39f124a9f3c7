"""TransformerLM — flagship SPMD language model (pure-functional).

The reference's largest-scale story is data-parallel ResNet/LSTM via KVStore
(SURVEY.md §2.3); it predates tensor/sequence parallelism.  A TPU-native
framework must treat those as first-class, so this model is written directly
against the mesh axes of mxnet_tpu.parallel.mesh:

  - batch            -> 'dp'
  - attention heads / MLP hidden -> 'tp'   (Megatron-style column/row splits)
  - sequence         -> 'sp'   (ring attention, parallel/ring_attention.py)
  - layers are stacked and scanned (lax.scan) — the stacking dimension is the
    natural pipeline ('pp') axis for later stages.

Everything is a dict pytree of jax arrays + a dict of PartitionSpecs; the
fused train step (parallel/trainer.py) or any jax transform composes with it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import kernels as _kernels
from .. import runtime as _runtime
from ..parallel.ring_attention import ring_self_attention_sharded

__all__ = ["TransformerLMConfig", "TransformerLM"]

#: what a dispatch's next-token choice runs, by :func:`sample_tier`
SAMPLE_TIERS = ("argmax", "gumbel", "sorted")


def sample_tier(temperature, top_k, top_p):
    """Index into ``SAMPLE_TIERS`` of the work a batch's sampling controls
    ask for: 0 where no row samples (``temperature`` 0 throughout), 1
    where rows sample and none of THEM truncates, 2 where a sampled row
    has ``top_k > 0`` or ``top_p < 1``.  Written once for the device
    (``jnp`` arrays: the branch ``TransformerLM._choose`` takes) and the
    host (``numpy``: what the engine counts), so the two cannot drift."""
    sampled = temperature > 0
    truncates = sampled & ((top_k > 0) | (top_p < 1))
    return sampled.any().astype("int32") + truncates.any().astype("int32")


class TransformerLMConfig:
    def __init__(self, vocab_size=32000, num_layers=12, d_model=768,
                 num_heads=12, d_ff=3072, max_len=2048,
                 dtype=jnp.bfloat16, causal=True):
        assert d_model % num_heads == 0
        self.vocab_size = vocab_size
        self.num_layers = num_layers
        self.d_model = d_model
        self.num_heads = num_heads
        self.head_dim = d_model // num_heads
        self.d_ff = d_ff
        self.max_len = max_len
        self.dtype = dtype
        self.causal = causal


def _norm(x, scale, eps=1e-6):
    # RMSNorm in fp32 for stability, output in model dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _write_kv(pools, layer, pages, slots, k, v):
    """One layer's new K/V ``[B, H, S, Dh]`` into the page pools, in place:
    position ``[b, s]`` becomes row ``[layer, pages[b, s], slots[b, s]]``
    of the whole ``[L, P, psz, H*Dh]`` pools (the sentinel page drops).
    Int8 pools (``"k_scale" in pools``) quantize each row on the way in
    and their ``[L, P, psz, H]`` scale pools take the scales.  Returns
    the pools, a new dict."""
    B, _, S, _ = k.shape
    pools = dict(pools)
    with jax.named_scope("mx.kv_write"):
        for key, val in (("k", k), ("v", v)):
            rows = jnp.transpose(val, (0, 2, 1, 3))           # [B,S,H,Dh]
            if key + "_scale" in pools:
                from .. import quantization as _quant
                rows, scales = _quant.quantize_rows(rows)
                pools[key + "_scale"] = pools[key + "_scale"].at[
                    layer, pages, slots].set(scales, mode="drop")
            pools[key] = pools[key].at[layer, pages, slots].set(
                rows.astype(pools[key].dtype).reshape(B, S, -1),
                mode="drop")
    return pools


class TransformerLM:
    """Decoder-only transformer; params stacked over layers and scanned."""

    def __init__(self, config, mesh=None):
        self.cfg = config
        self.mesh = mesh
        names = mesh.axis_names if mesh is not None else ()
        self._dp = "dp" if "dp" in names else None
        self._tp = "tp" if "tp" in names else None
        self._sp = "sp" if "sp" in names else None

    # -------------------------------------------------------------- params
    def init(self, key):
        cfg = self.cfg
        k = jax.random.split(key, 8)
        D, F, V, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.num_layers
        H, Dh = cfg.num_heads, cfg.head_dim
        init = jax.nn.initializers.normal(0.02)

        def mk(kk, shape, fan_in=None):
            w = init(kk, shape, jnp.float32)
            if fan_in:
                w = w / math.sqrt(fan_in / D)
            return w.astype(cfg.dtype)

        params = {
            "embed": mk(k[0], (V, D)),
            "pos_embed": mk(k[1], (cfg.max_len, D)),
            "final_norm": jnp.ones((D,), cfg.dtype),
            "layers": {
                "ln1": jnp.ones((L, D), cfg.dtype),
                "wqkv": mk(k[2], (L, D, 3, H, Dh)),
                "wo": mk(k[3], (L, H, Dh, D)),
                "ln2": jnp.ones((L, D), cfg.dtype),
                "w1": mk(k[4], (L, D, F)),
                "w2": mk(k[5], (L, F, D)),
            },
        }
        return params

    def param_specs(self):
        """PartitionSpec per param — Megatron column/row splits on 'tp'."""
        tp = self._tp
        return {
            "embed": P(None, None),
            "pos_embed": P(None, None),
            "final_norm": P(None),
            "layers": {
                "ln1": P(None, None),
                "wqkv": P(None, None, None, tp, None),
                "wo": P(None, tp, None, None),
                "ln2": P(None, None),
                "w1": P(None, None, tp),
                "w2": P(None, tp, None),
            },
        }

    # -------------------------------------------------------------- forward
    def _constrain(self, x, *spec):
        if self.mesh is None:
            return x
        return lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, P(*spec)))

    def _attention(self, q, k, v):
        # q,k,v: [B, H, S, Dh]
        if self.mesh is not None and self._sp is not None and \
                self.mesh.shape.get(self._sp, 1) > 1:
            return ring_self_attention_sharded(
                self.mesh, q, k, v, causal=self.cfg.causal,
                batch_axis=self._dp, head_axis=self._tp, seq_axis=self._sp)
        # mx.kernels routes to the fused Pallas flash kernel when the
        # tier is on and the shape qualifies; otherwise (and by default)
        # this IS the plain XLA attention lowering
        attend = partial(_kernels.attention, causal=self.cfg.causal)
        axes = tuple(a for a in (self._dp, self._tp)
                     if a is not None and self.mesh.shape[a] > 1)
        if axes and _kernels.enabled():
            # a Mosaic kernel cannot be partitioned by the compiler: on a
            # mesh that splits batch or heads the routed call runs per
            # shard, and the route is picked at the shard's shape.
            # Attention is independent per (batch, head): the body holds
            # no collective, so there is nothing for the varying-axes
            # check to check (and the Pallas interpreter's own loops trip
            # it).  Tier off keeps the plain global call.
            spec = P(self._dp, self._tp, None, None)
            return jax.shard_map(
                attend, mesh=self.mesh, in_specs=(spec, spec, spec),
                out_specs=spec, axis_names=frozenset(axes),
                check_vma=False)(q, k, v)
        return attend(q, k, v)

    def _qkv(self, x, lp):
        """ln1 + fused QKV projection: x [B,S,D] -> q,k,v [B,H,S,Dh]."""
        with jax.named_scope("mx.qkv"):
            h = _norm(x, lp["ln1"])
            qkv = jnp.einsum("bsd,dche->bsche", h, lp["wqkv"],
                             preferred_element_type=jnp.float32
                             ).astype(x.dtype)
            q = jnp.transpose(qkv[:, :, 0], (0, 2, 1, 3))   # [B,H,S,Dh]
            k = jnp.transpose(qkv[:, :, 1], (0, 2, 1, 3))
            v = jnp.transpose(qkv[:, :, 2], (0, 2, 1, 3))
            return q, k, v

    def _attn_mlp(self, x, o, lp):
        """Output projection + residual + MLP half of one layer; ``o`` is
        the attention output [B,H,S,Dh]."""
        with jax.named_scope("mx.attn_out"):
            o = jnp.einsum("bhse,hed->bsd", o, lp["wo"],
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)
            x = x + o
            x = self._constrain(x, self._dp, self._sp, None)

        with jax.named_scope("mx.mlp"):
            h = _norm(x, lp["ln2"])
            u = jnp.einsum("bsd,df->bsf", h, lp["w1"],
                           preferred_element_type=jnp.float32)
            u = jax.nn.gelu(u).astype(x.dtype)
            u = self._constrain(u, self._dp, self._sp, self._tp)
            d = jnp.einsum("bsf,fd->bsd", u, lp["w2"],
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)
            x = x + d
            return self._constrain(x, self._dp, self._sp, None)

    def _layer(self, x, lp, kv_sink=None):
        q, k, v = self._qkv(x, lp)
        if kv_sink is not None:
            # generation prefill: the per-layer K/V stream is ALSO written
            # into the paged cache; the attention math below is untouched,
            # which is what keeps prefill logits on the eager apply() path
            kv_sink(k, v)
        q = self._constrain(q, self._dp, self._tp, self._sp, None)
        k = self._constrain(k, self._dp, self._tp, self._sp, None)
        v = self._constrain(v, self._dp, self._tp, self._sp, None)
        with jax.named_scope("mx.attention"):
            o = self._attention(q, k, v)                # [B,H,S,Dh]
        return self._attn_mlp(x, o, lp)

    def run_stack(self, params, x):
        """Shared encoder body: sharding constraint -> scanned layers ->
        final norm.  Used by apply() and by models embedding differently
        before the stack (models/bert.py)."""
        x = self._constrain(x, self._dp, self._sp, None)
        from .. import numerics as _numerics
        if _numerics.collecting():
            # per-layer stats ride the scan as ys, so scan-over-layers
            # still compiles the layer body once; the (L, 6) stack is
            # expanded to layer_out[i] sites host-side
            def body(carry, lp):
                out = self._layer(carry, lp)
                return out, _numerics.summarize(out)

            x, ys = _runtime.scan_stack(body, x, params["layers"])
            _numerics.tap_stacked("layer_out", ys)
            return _norm(x, params["final_norm"])

        def body(carry, lp):
            return self._layer(carry, lp), None

        # runtime.scan_stack applies the knob-selected scan/unroll +
        # remat policy; at default knobs it is exactly lax.scan(body, ...)
        x, _ = _runtime.scan_stack(body, x, params["layers"])
        return _norm(x, params["final_norm"])

    def apply(self, params, tokens):
        """tokens [B, S] int32 -> logits [B, S, V] (fp32)."""
        cfg = self.cfg
        S = tokens.shape[1]
        x = params["embed"][tokens] + params["pos_embed"][:S][None]
        x = self.run_stack(params, x.astype(cfg.dtype))
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"],
                            preferred_element_type=jnp.float32)
        return logits

    def loss(self, params, tokens, targets):
        """Mean next-token cross entropy; targets [B, S] int32."""
        logits = self.apply(params, tokens)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    # --------------------------------------------- generation (paged KV)
    # Autoregressive serving state (docs/SERVING.md "Generation"): the KV
    # cache is a POOL of fixed-size pages shared by every in-flight
    # sequence; each sequence owns a page-table row of page ids.  Position
    # t of a sequence lives in page ``table[t // page_size]`` at slot
    # ``t % page_size``, as one row of all heads' K (or V): a page is
    # ``[page_size, H*Dh]``, lane-exact and contiguous on the device, so
    # the decode kernel copies it where it lies.  A page id >= num_pages
    # is the SENTINEL: writes through it drop (jax scatter mode="drop")
    # and reads through it clip to a real page whose rows the row's
    # length then masks out — padded table entries and inactive decode
    # slots are branch-free.

    def kv_spec(self, quantized=False):
        """Static description of one model's page pool — what deploy.py
        stamps into the v4/v5 meta so a server can allocate the pool
        without reconstructing the model.  ``quantized`` describes int8
        KV pages: int8 payload pools plus per-(slot, head) f32 scale
        pools, HALF the HBM per cached token.  ``row_width`` says a slot
        is one ``H*Dh`` row (artifacts of older builds, whose pages were
        ``[page_size, H, Dh]``, lack it and are refused at load)."""
        cfg = self.cfg
        spec = {"num_layers": cfg.num_layers, "num_heads": cfg.num_heads,
                "head_dim": cfg.head_dim,
                "row_width": cfg.num_heads * cfg.head_dim,
                "dtype": jnp.dtype(cfg.dtype).name}
        if quantized:
            spec["quantized"] = True
        return spec

    def init_kv_pages(self, num_pages, page_size, quantized=False):
        """Zeroed device page pool: {"k","v"} of
        [L, num_pages, page_size, H*Dh] in the model dtype; with
        ``quantized`` the payload is int8 and per-row scales ride along
        as {"k_scale","v_scale"} of [L, num_pages, page_size, H] f32."""
        cfg = self.cfg
        shape = (cfg.num_layers, int(num_pages), int(page_size),
                 cfg.num_heads * cfg.head_dim)
        if quantized:
            scales = shape[:-1] + (cfg.num_heads,)
            return {"k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(scales, jnp.float32),
                    "v_scale": jnp.zeros(scales, jnp.float32)}
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}

    def _logits_last(self, params, x):
        """Final norm + tied-embedding readout for one position per row:
        x [B, D] -> logits [B, V] f32."""
        with jax.named_scope("mx.lm_head"):
            x = _norm(x, params["final_norm"])
            return jnp.einsum("bd,vd->bv", x, params["embed"],
                              preferred_element_type=jnp.float32)

    def _sample_last(self, params, x, positions, sample):
        """Readout + next-token choice for one position per row.

        ``sample`` None = greedy (argmax — the bitwise oracle contract).
        Otherwise a dict of per-row arrays: ``temperature`` [B] f32
        (0 = greedy for that row), ``top_k`` [B] i32 (0 = off),
        ``top_p`` [B] f32 (1 = off), ``key`` [B, 2] uint32 raw PRNG key
        data.  The row key is folded with the POSITION OF THE TOKEN
        BEING SAMPLED, so a fixed request seed yields one deterministic
        stream regardless of batch composition or dispatch order — the
        sampling-determinism contract of tools/check_generation.py.
        Sampling is Gumbel-max over the temperature-scaled, top-k/top-p
        masked logits; rows with temperature 0 take the UNSCALED argmax,
        bitwise the greedy readout.  Returns ``(ids [B] i32,
        logits [B, V] f32)`` — raw logits, for the int8 drift gate."""
        logits = self._logits_last(params, x)
        with jax.named_scope("mx.sample"):
            return self._choose(logits, positions, sample), logits

    def _choose(self, logits, positions, sample):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if sample is None:
            return greedy
        temp = sample["temperature"].astype(jnp.float32)        # [B]
        top_k = sample["top_k"].astype(jnp.int32)               # [B]
        top_p = sample["top_p"].astype(jnp.float32)             # [B]
        keys = sample["key"].astype(jnp.uint32)                 # [B, 2]
        V = logits.shape[-1]

        def scale():
            return logits / jnp.where(temp > 0, temp, 1.0)[:, None]

        def draw(masked):
            gum = jax.vmap(lambda kr, pos: jax.random.gumbel(
                jax.random.fold_in(kr, pos), (V,), jnp.float32))(
                    keys, positions.astype(jnp.uint32))
            return jnp.argmax(masked + gum, axis=-1).astype(jnp.int32)

        def truncated():
            scaled = scale()
            # (lax.sort is the operation jnp.sort lowers to, without the
            # jit of its own that drops the scope's name)
            sorted_desc = lax.sort(scaled, dimension=-1)[:, ::-1]
            # top-k: the kth-largest scaled logit is the row threshold
            k_idx = jnp.clip(top_k - 1, 0, V - 1)
            kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
            keep = jnp.where((top_k > 0)[:, None], scaled >= kth, True)
            # top-p (nucleus): keep the smallest sorted prefix whose
            # probability mass reaches p — token i survives while the mass
            # BEFORE it is < p, so the first token always survives
            probs = jax.nn.softmax(sorted_desc, axis=-1)
            csum = jnp.cumsum(probs, axis=-1)
            in_nucleus = (csum - probs) < top_p[:, None]
            thr = jnp.min(jnp.where(in_nucleus, sorted_desc, jnp.inf),
                          axis=-1, keepdims=True)
            keep &= jnp.where((top_p < 1.0)[:, None], scaled >= thr, True)
            return draw(jnp.where(keep, scaled, -jnp.inf))

        # the batch pays for the tier its own controls ask for (SAMPLE_TIERS):
        # no sampled row, the arg-max alone; none of them truncating, no
        # sort either (every token is kept, so the mask is the identity)
        choice = lax.switch(sample_tier(temp, top_k, top_p),
                            (lambda: greedy, lambda: draw(scale()),
                             truncated))
        return jnp.where(temp > 0, choice, greedy)

    def _scan_layers_over_pools(self, body, x, params, kv):
        """``body((x, pools), (layer_params, layer)) -> ((x, pools), None)``
        over the layers; returns ``(x, pools)``.  The page pools ride the
        scan as its carry, whole, and each layer writes its rows in place
        at its index: as a scanned input and a stacked output every
        layer's pool would be sliced out and written back, and the stack
        copied besides."""
        layers = jnp.arange(self.cfg.num_layers, dtype=jnp.int32)
        (x, kv), _ = _runtime.scan_stack(body, (x, kv),
                                         (params["layers"], layers))
        return x, kv

    def prefill(self, params, kv, tokens, lengths, page_table, page_size,
                sample=None, return_logits=False):
        """Process whole prompts and seed the paged cache.

        tokens [B, S] int32 (rows padded past ``lengths`` with anything),
        lengths [B] int32 true prompt lengths, page_table [B, W] int32
        with W*page_size >= S.  Runs the standard causal stack — the
        attention seen by position ``lengths-1`` is exactly ``apply()``'s,
        so the returned greedy next token matches the eager oracle —
        while every layer's K/V stream is scattered into the page pool.
        An int8 pool (``"k_scale" in kv``) quantizes each row on the way
        into the pages; prefill attention itself reads the full-precision
        stream, so the FIRST generated token is untouched by KV
        quantization.  ``sample`` (see :meth:`_sample_last`) draws the
        next token; None = greedy.  Returns ``(new_kv, next_token[B]
        int32)``, plus the next-token logits with ``return_logits``.
        """
        cfg = self.cfg
        B, S = tokens.shape
        psz = int(page_size)
        pool = kv["k"].shape[1]
        x = (params["embed"][tokens]
             + params["pos_embed"][:S][None]).astype(cfg.dtype)
        x = self._constrain(x, self._dp, self._sp, None)

        iota = jnp.arange(S, dtype=jnp.int32)
        pages = page_table[:, iota // psz]                    # [B, S]
        # positions past the true prompt length write through the OOB
        # sentinel and are dropped
        pages = jnp.where(iota[None, :] < lengths[:, None], pages, pool)
        slots = jnp.broadcast_to(iota % psz, (B, S))

        def body(carry, xs):
            x, pools = carry
            lp, layer = xs
            new = {}
            out = self._layer(x, lp, kv_sink=lambda k, v: new.update(
                _write_kv(pools, layer, pages, slots, k, v)))
            return (out, new), None

        x, nkv = self._scan_layers_over_pools(body, x, params, kv)
        last = jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None]
            .astype(jnp.int32), axis=1)[:, 0]                 # [B, D]
        ids, logits = self._sample_last(params, last, lengths, sample)
        if return_logits:
            return nkv, ids, logits
        return nkv, ids

    def decode_step(self, params, kv, token_ids, positions, page_table,
                    page_size, sample=None, return_logits=False):
        """One generation iteration for a whole decode batch.

        token_ids [B] int32 (the token to append), positions [B] int32
        (its position = tokens already cached), page_table [B, W] int32.
        Appends each token's K/V to its page, then attends over the row's
        ``positions + 1`` tokens through ``kernels.paged_attention``, which
        is handed the whole pool, the layer's index, the page table and
        those lengths: on the kernel route the pages are read where they
        lie, each row's and no more; on the XLA twin's route the table's
        whole window is gathered first (``mx.kv_gather``).  The pool is
        the layer scan's carry, written in place (donated, it comes back
        in the buffer it came in).  Returns
        ``(new_kv, next_token[B] int32)``.  Inactive slots pass the
        sentinel page everywhere and position 0: their write drops, they
        read one clamped page, and their output is garbage the scheduler
        ignores.  With an int8 pool the appended row quantizes into the
        pages and the pools' per-row scale pages go along, to dequantize
        in the consumer (inside the Pallas kernel's VMEM pass on the
        kernel route).  ``sample``/``return_logits`` as in
        :meth:`prefill`.
        """
        cfg = self.cfg
        psz = int(page_size)
        x = (params["embed"][token_ids]
             + params["pos_embed"][positions]).astype(cfg.dtype)[:, None]
        page = jnp.take_along_axis(
            page_table, (positions // psz)[:, None], axis=1)  # [B,1]
        slot = (positions % psz)[:, None]                     # [B,1]

        def body(carry, xs):
            x, pools = carry
            lp, layer = xs
            q, k, v = self._qkv(x, lp)                        # [B,H,1,Dh]
            pools = _write_kv(pools, layer, page, slot, k, v)
            # after the write, so the row attends over its own new token
            o = _kernels.paged_attention(
                q, pools["k"], pools["v"], page_table, positions + 1,
                k_scale=pools.get("k_scale"), v_scale=pools.get("v_scale"),
                layer=layer)
            return (self._attn_mlp(x, o, lp), pools), None

        x, nkv = self._scan_layers_over_pools(body, x, params, kv)
        ids, logits = self._sample_last(params, x[:, 0], positions + 1,
                                        sample)
        if return_logits:
            return nkv, ids, logits
        return nkv, ids

    def greedy_decode(self, params, prompt, max_new_tokens, eos_id=None):
        """Cache-free greedy-decode reference: a FULL re-forward of the
        whole sequence per token.  The bitwise parity oracle for the
        prefill + decode-step path (tools/check_generation.py) — slow by
        design, trust anchor only.  The sequence is zero-padded to
        ``cfg.max_len`` so every re-forward reuses ONE compiled program;
        causal attention's masked keys contribute exact zeros, so the
        logits at real positions are bitwise those of the unpadded
        forward.  ``prompt`` is a 1-D int sequence; returns the generated
        ids (eos included when hit) as np.int32."""
        import numpy as _np
        S = self.cfg.max_len
        fwd = getattr(self, "_oracle_fwd", None)
        if fwd is None:
            fwd = self._oracle_fwd = jax.jit(
                lambda ps, toks: self.apply(ps, toks))
        toks = _np.asarray(prompt, _np.int32).reshape(-1)
        n = int(toks.shape[0])
        if n + int(max_new_tokens) > S:
            raise ValueError(
                "prompt (%d) + max_new_tokens (%d) exceeds max_len %d"
                % (n, max_new_tokens, S))
        buf = _np.zeros((1, S), _np.int32)
        buf[0, :n] = toks
        out = []
        for _ in range(int(max_new_tokens)):
            logits = fwd(params, jnp.asarray(buf))
            nxt = int(jnp.argmax(logits[0, n - 1]))
            out.append(nxt)
            if n < S:
                buf[0, n] = nxt
            n += 1
            if eos_id is not None and nxt == int(eos_id):
                break
        return _np.asarray(out, _np.int32)
