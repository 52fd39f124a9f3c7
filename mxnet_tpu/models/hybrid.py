"""HybridLM — a decoder whose blocks are each ONE mixer, of eleven kinds.

The stack is a pattern string, one letter a block (the ``nemotron_h``
family's ``hybrid_override_pattern``, and two letters of this module's
own):

  ``M``  Mamba-2 (state-space duality): a gated, depthwise-convolved
         recurrence with a fixed-size state per sequence
  ``E``  a mixture of experts that live in a latent width, chosen by a
         sigmoid-scored top-k router, beside one shared expert
  ``*``  causal softmax attention with fewer K/V heads than query heads
  ``R``  power retention of degree 2 (Gelada, Buckman et al.,
         arXiv:2507.04239): attention weights ``(q.k)^2`` under a learned
         per-K/V-head decay, normalised to sum to one, computed as a
         recurrence over the symmetric square ``phi(k)`` of the key — a
         fixed-size state per K/V head that its query heads all read;
         per-head RMSNorm of q and k, rotary positions
  ``F``  a gated (SwiGLU) MLP
  ``L``  multi-head latent attention (DeepSeek-V2, arXiv:2405.04434
         section 2.1): queries through a low-rank path with its own
         RMSNorm, keys and values through ONE low-rank latent a token
         (its own RMSNorm) beside ONE rotary key that all heads share;
         each head's query and key have an un-rotated and a rotary part,
         its value a width of its own.  The cache keeps the latent and the
         rotary key, not K and V: a whole prompt attends in the EXPANDED
         form (keys and values up-projected, one flash pass), a decode
         step in the ABSORBED one (the keys' up-projection folded into the
         query, the values' applied to the attended latent) over the rows
         the pages hold (``kernels.latent_paged_attention``)
  ``G``  a mixture of GATED experts at the model's full width (DeepSeek-V3,
         arXiv:2412.19437 section 2.1): ``W_down(silu(W_gate x) * W_up
         x)`` an expert, chosen by ``E``'s sigmoid-scored, bias-selected,
         normalised top-k router, beside one shared gated expert
  ``S``  ``L`` with learned SPARSE selection (DeepSeek Sparse Attention,
         the DeepSeek-V3.2-Exp report section 2.1): an indexer scores
         every earlier token, ``I_ts = sum_j w_tj relu(q^I_tj . k^I_s)``
         (``index_heads`` queries from the query latent, one LayerNorm'd
         key a token, rotary on ``rope_dim`` of their dims), and each
         query attends over its ``index_topk`` highest-scoring tokens
         only — a decode step in the absorbed form over the pages, a whole
         prompt in the expanded form, one flash pass masked to the
         selection (``kernels.sparse_prefill_route``); a token's index
         key rides in its page below its latent row
  ``W``  ``L`` over a sliding WINDOW of the ``window`` latest positions,
         with sizes of its own (``swa_*``) and no page: each decode slot
         keeps the window's latent rows in a ring of ``ring`` columns
         (position p at column p mod ring) in the state arrays

  ``B``  grouped-query softmax attention over BLOCKS chosen by compressed
         keys (InfLLM-v2, the MiniCPM4 report arXiv:2506.07900 section
         2.1): per-head RMSNorm of q and k, no positional encoding; the
         mean key of every ``kernel_size`` positions at stride
         ``kernel_stride`` scores each query's relevance per K/V head
         (softmax over the compressed keys, summed over the group's query
         heads, the largest over the kernels a ``block_size`` block
         overlaps), and each query attends over the first
         ``init_blocks`` blocks, the blocks of its ``local_window`` latest
         positions and the best of the rest, ``block_topk`` blocks in all
         (every block while the position is under ``dense_len``).  A page
         is a block: a decode step attends over the row's selected pages
         through the paged kernel (``kernels.block_sparse_attention``), a
         prompt through one causal flash pass masked a block at a time
         (``kernels.block_sparse_prefill``); the compressed keys live in
         the state arrays, one row a ``kernel_stride`` positions, written
         as each kernel completes
  ``N``  lightning attention (Qin et al., arXiv:2401.04658; MiniMax-01,
         arXiv:2501.08313 section 2.2): linear attention under a FIXED
         per-head decay, ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t =
         q_t S_t / sqrt(Dh)``, float32 state ``[heads, Dh, Dh]`` a slot;
         SiLU on q, k and v, per-head RMSNorm and rotary on q and k,
         RMSNorm over the heads' concatenated output, an elementwise
         sigmoid gate; ``lambda_h = exp(-2^(-8(h+1)/H) (1 - l/(L-1) +
         1e-5))`` of the block's PUBLISHED layer ``l`` of ``L``

``S``, ``W`` and ``B`` blocks gate each head's output by one sigmoid of the
block's normalised input (Qiu et al., arXiv:2505.06708).

The MiniCPM family's muP scalars are configuration: the embedding times
``scale_emb``, every block's output times ``residual_scale`` before it
joins the residual stream, the final normalised state times
``logit_scale`` before the head; at their defaults of 1 no operation is
added.

``h <- h + Mixer(RMSNorm(h))`` per block, no positional table (the
recurrences order the tokens; ``R`` and ``L`` rotate q and k by their
positions), an output head of its own.  The class has
``TransformerLM``'s generation protocol (``cfg``, ``init``, ``apply``,
``kv_spec``, ``init_kv_pages``, ``prefill``, ``decode_step``,
``greedy_decode``) and so goes through ``deploy.export_generation`` and
the generation server unchanged; what differs is the cache it describes:
K/V pages for the layers that attend, and per decode slot a float32
recurrent state and a convolution tail for every ``M`` layer and a
float32 retention state and its normaliser for every ``R`` layer
(``kv_spec()["state"]``).  A prefill leaves the prompt's final state in
the slot it is told; a decode step advances every slot's.  A pattern
without ``*`` or ``L`` keeps no page at all (``kv_spec()["num_layers"] ==
0``).  ``L`` layers keep ONE pool, ``kv`` ``[L layers, pages, kv_rank +
rope_dim, page_size]``: a token's row is its normalised latent beside its
rotated shared key (576 bf16 at the published widths, where K and V of 32
heads would be 10,240), and a page holds its tokens ON THE LANES (row c of
a page is component c of its tokens: a 576-wide minor axis would be padded
to 640 lanes, or laid out this way by the compiler behind a copy into
every kernel call); ``kv_spec()`` says so (``pools``, ``page_layout``) and
``deploy`` / the engine build the cache from that description.  A model
keeps one kind of page: a pattern may not mix ``*``, ``L``, ``S`` and
``B``.  ``S`` pages are ``L``'s with ``index_dim`` more rows a page, the
index keys (``kv_spec()["index_rows"]``).  ``B`` pages are K and V pages
of ``block_size`` tokens with each K/V head's rows apart (``page_layout``
``"heads"``: ``[L layers, pages, KVH, page_size, Dh]``, so a page of one
head is one contiguous copy), and per decode slot the state region holds
each ``B`` block's compressed keys and the running key sums that complete
the next one, and each ``N`` block's float32 ``[H, Dh, Dh]`` state.

One chip's share of an expert-parallel deployment is a configuration, not
another code path: ``experts_held`` / ``expert_offset`` say which routed
experts live here (the router stays ``num_experts`` wide), and what the
absent ones would add is left out (``parallel.moe.dropless_experts``).

Parameters are a dict per block (``params["layers"]["07"]``), not stacked
per kind: a block's weights go whole into the products that read them (the
grouped product of the experts is a kernel, and a slice of a stacked array
handed to a kernel is a copy of it, 0.7 GB a layer at the published
sizes), and each state array is donated and rewritten whole, in place.
The blocks are walked in Python, so no pool or state rides a scan.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import kernels as _kernels
from ..parallel import moe as _moe
from .transformer import TransformerLM, _norm

__all__ = ["HybridLMConfig", "HybridLM"]

KINDS = "ME*RFLGSWBN"
#: what ``parallel.moe.dropless_experts`` counts, summed over the E and G
#: blocks
_STATS = ("pairs", "experts_hit", "max_load")
#: ``S`` blocks' prefill: queries a step of its loop (each step holds the
#: chunk's index scores over the whole prompt, float32)
_DSA_QUERY_CHUNK = 64
#: ... in this many causal segments, each reading the keys before its end
_DSA_SEGMENTS = 4
#: the indexer's LayerNorm (DeepSeek's default)
_INDEX_LN_EPS = 1e-6
#: the float32 pair products one expert layer may hold at once, a
#: sixteenth of a 16 GiB chip: a layer whose products need more runs its
#: token rows in chunks (:func:`_in_row_chunks`)
_MOE_PAIR_BYTES = 1 << 30
#: what the ``S`` and ``W`` blocks count in a decode step, after
#: :data:`_STATS` (``decode_step(..., return_stats=True)``)
_SPARSE_STATS = ("index_tokens", "selected_tokens")
_RING_STATS = ("ring_tokens",)
#: ... and what the ``B`` blocks count: compressed keys scored and tokens
#: attended (a query head's, averaged over the K/V heads)
_BLOCK_STATS = ("compressed_keys", "selected_tokens")
#: ``B`` blocks' prefill: queries a step of the selection's loop (each step
#: holds the chunk's relevance over every compressed key, float32)
_BLOCK_QUERY_CHUNK = 512


class HybridLMConfig:
    def __init__(self, vocab_size=32000, pattern="MEM*E", d_model=512,
                 num_heads=8, num_kv_heads=2, head_dim=64, ssm_heads=16,
                 ssm_head_dim=64, ssm_groups=2, ssm_state=64,
                 conv_kernel=4, chunk=128, num_experts=16, top_k=2,
                 moe_latent=128, expert_ff=256, shared_ff=512,
                 route_scale=1.0, experts_held=None, expert_offset=0,
                 max_len=2048, dtype=jnp.bfloat16, eps=1e-5, depth=None,
                 rope_theta=1e6, mlp_ff=1024, q_rank=128, kv_rank=64,
                 nope_dim=32, rope_dim=16, v_dim=32, index_heads=4,
                 index_dim=16, index_topk=8, swa_heads=4, swa_q_rank=64,
                 swa_kv_rank=32, swa_nope_dim=16, swa_rope_dim=8,
                 swa_v_dim=16, swa_rope_theta=5e4, window=9,
                 kernel_size=32, kernel_stride=16, block_size=64,
                 block_topk=64, init_blocks=1, local_window=2048,
                 dense_len=8192, first_layer=0,
                 published_layers=None, scale_emb=1.0, residual_scale=1.0,
                 logit_scale=1.0):
        if not pattern or set(pattern) - set(KINDS):
            raise ValueError("pattern %r: one of %r per block"
                             % (pattern, KINDS))
        if num_heads % num_kv_heads or ssm_heads % ssm_groups:
            raise ValueError("query heads must be a multiple of the K/V "
                             "heads, and the state heads of their groups")
        self.vocab_size = vocab_size
        self.pattern = pattern
        self.num_layers = len(pattern)
        self.d_model = d_model
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.ssm_heads = ssm_heads
        self.ssm_head_dim = ssm_head_dim
        self.ssm_groups = ssm_groups
        self.ssm_state = ssm_state
        self.ssm_inner = ssm_heads * ssm_head_dim
        #: the convolved stream: X and the groups' B and C
        self.conv_width = self.ssm_inner + 2 * ssm_groups * ssm_state
        self.conv_kernel = conv_kernel
        self.chunk = chunk
        self.num_experts = num_experts
        self.top_k = top_k
        self.moe_latent = moe_latent
        self.expert_ff = expert_ff
        self.shared_ff = shared_ff
        self.route_scale = route_scale
        self.experts_held = num_experts if experts_held is None \
            else int(experts_held)
        self.expert_offset = int(expert_offset)
        if not 0 <= self.expert_offset \
                <= num_experts - self.experts_held:
            raise ValueError("held experts %d+%d lie outside the %d routed"
                             % (self.expert_offset, self.experts_held,
                                num_experts))
        self.max_len = max_len
        self.dtype = dtype
        self.eps = eps
        self.causal = True
        #: blocks in the whole stack, where ``pattern`` is one pipeline
        #: stage of a deeper one: ``init`` scales by it
        self.depth = len(pattern) if depth is None else int(depth)
        #: ``R`` blocks: the rotary base, and the width of one K/V head's
        #: state (the upper triangle of a head_dim x head_dim square: the
        #: retention's degree is 2)
        self.rope_theta = float(rope_theta)
        self.ret_width = head_dim * (head_dim + 1) // 2
        #: ``F`` blocks: the gated MLP's inner width
        self.mlp_ff = mlp_ff
        #: ``L`` blocks: the ranks of the query and key/value latents, a
        #: head's un-rotated and rotary query/key widths and its value
        #: width (``num_heads`` heads; ``rope_theta`` rotates)
        self.q_rank = q_rank
        self.kv_rank = kv_rank
        self.nope_dim = nope_dim
        self.rope_dim = rope_dim
        self.v_dim = v_dim
        #: ``S`` blocks (``L``'s sizes and rotary base; the index queries and
        #: keys rotate their first ``rope_dim`` dims): the indexer's heads,
        #: their width, and the tokens a query keeps
        self.index_heads = index_heads
        self.index_dim = index_dim
        self.index_topk = index_topk
        #: ``W`` blocks: ``L``'s sizes of their own, the positions a query
        #: sees (its own among them) and the columns of a slot's ring (the
        #: window in whole 128-lane tiles)
        self.swa_heads = swa_heads
        self.swa_q_rank = swa_q_rank
        self.swa_kv_rank = swa_kv_rank
        self.swa_nope_dim = swa_nope_dim
        self.swa_rope_dim = swa_rope_dim
        self.swa_v_dim = swa_v_dim
        self.swa_rope_theta = float(swa_rope_theta)
        self.window = int(window)
        self.ring = -(-self.window // 128) * 128
        #: ``B`` blocks (``num_heads`` over ``num_kv_heads`` of
        #: ``head_dim``): a compressed key is the mean of ``kernel_size``
        #: keys, one every ``kernel_stride`` positions; a block (and a page)
        #: is ``block_size`` positions; a query keeps ``block_topk`` blocks
        #: (the first ``init_blocks`` and those of its ``local_window``
        #: latest positions among them) or, under ``dense_len``, every one
        self.kernel_size = int(kernel_size)
        self.kernel_stride = int(kernel_stride)
        self.block_size = int(block_size)
        self.block_topk = int(block_topk)
        self.init_blocks = int(init_blocks)
        self.local_window = int(local_window)
        self.dense_len = int(dense_len)
        if "B" in pattern and (
                kernel_size % kernel_stride or block_size % kernel_stride
                or max_len % block_size or max_len % kernel_stride):
            raise ValueError(
                "B blocks: kernel %d and block %d must be whole strides of "
                "%d, and max_len %d whole blocks" % (
                    kernel_size, block_size, kernel_stride, max_len))
        #: ``N`` blocks: the published index of the pattern's first layer
        #: and the published depth the decay reads (a layer is a mixer
        #: block and what follows it up to the next mixer)
        self.first_layer = int(first_layer)
        self.published_layers = int(
            published_layers if published_layers is not None
            else sum(k != "F" for k in pattern))
        #: muP: the embedding's, every block output's and the final
        #: state's multipliers (1: no operation)
        self.scale_emb = float(scale_emb)
        self.residual_scale = float(residual_scale)
        self.logit_scale = float(logit_scale)
        if sum(k in pattern for k in "*LSB") > 1:
            raise ValueError(
                "pattern %r mixes '*' (K and V pages), 'L' (latent pages), "
                "'S' (latent pages with index keys) and 'B' (K and V pages "
                "a head apart): a model keeps one kind of page" % (pattern,))


def _normal(key, shape, std, dtype):
    """Normal values in ``dtype``; a large stack is drawn a slab at a time
    so no float32 copy of the whole is ever held."""
    if len(shape) > 2 and math.prod(shape) > 1 << 24:
        return lax.map(lambda k: _normal(k, shape[1:], std, dtype),
                       jax.random.split(key, shape[0]))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


class HybridLM:
    """Pattern-built hybrid decoder; see the module text."""

    #: ``prefill`` / ``decode_step`` can say what replaying a served
    #: sequence elsewhere needs (``return_replay``): the experts every
    #: token chose in every ``E`` block, and the log-probability of the
    #: token each row produced
    replay = True
    #: the experts' grouped products run over token rows: an exported
    #: program of this model has concrete batch dims
    symbolic_batch = False

    def __init__(self, config):
        self.cfg = config
        self.kinds = tuple(config.pattern)
        self.names = tuple("%02d" % i for i in range(len(self.kinds)))
        # which attention layer (pool index) a block is
        self.attn_index = {n: i for i, n in enumerate(
            n for n, k in zip(self.names, self.kinds) if k in "*LSB")}
        self.latent = "L" in self.kinds or "S" in self.kinds
        self.sparse = "S" in self.kinds
        #: K and V pages a head apart, one a block (``B`` blocks)
        self.block_sparse = "B" in self.kinds
        #: what ``decode_step(..., return_stats=True)`` appends, in order:
        #: the experts' counts, then the rows the ``S`` blocks scored and
        #: attended and the ring columns the ``W`` blocks attended, where
        #: the pattern has them
        self.decode_stats = tuple("moe_" + n for n in _STATS) \
            + (_SPARSE_STATS if self.sparse else ()) \
            + (_RING_STATS if "W" in self.kinds else ()) \
            + (_BLOCK_STATS if self.block_sparse else ())
        #: ``L`` blocks: scores over a head's whole query/key width, in the
        #: expanded and in the absorbed form alike (``S`` and ``W`` blocks:
        #: over theirs)
        self._mla_scale = 1.0 / math.sqrt(config.nope_dim + config.rope_dim)
        self._swa_scale = 1.0 / math.sqrt(config.swa_nope_dim
                                          + config.swa_rope_dim)
        #: ``N`` blocks' log-decays [heads] by block name (constants)
        self._lit_log_decay = {}
        layer = config.first_layer
        for name, kind in zip(self.names, self.kinds):
            if kind == "N":
                self._lit_log_decay[name] = _lightning_log_decay(
                    config.num_heads, layer, config.published_layers)
            layer += kind != "F"

    # -------------------------------------------------------------- params
    def init(self, key):
        cfg = self.cfg
        D, dt = cfg.d_model, cfg.dtype
        keys = iter(jax.random.split(key, 16 * len(self.kinds) + 4))

        def mk(shape, fan_in, scale=1.0):
            # 0.02 at fan-in D, as TransformerLM
            return _normal(next(keys), shape,
                           scale * 0.02 / math.sqrt(fan_in / D), dt)

        # the family's ``rescale_prenorm_residual``: every block's last
        # matrix over sqrt(depth), so the whole stack moves a unit-variance
        # embedding by a fraction of its norm (a muP stack's residual
        # multiplier is its depth scaling: its matrices are drawn whole,
        # and its embedding so that the scaled one has unit variance)
        out = 1.0 / math.sqrt(cfg.depth) if cfg.residual_scale == 1.0 \
            else 1.0

        ones = lambda n: jnp.ones((n,), dt)   # noqa: E731
        layers = {}
        for name, kind in zip(self.names, self.kinds):
            if kind == "*":
                H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
                lp = {"ln": ones(D), "wq": mk((D, H, Dh), D),
                      "wk": mk((D, KV, Dh), D), "wv": mk((D, KV, Dh), D),
                      "wo": mk((H, Dh, D), H * Dh, out)}
            elif kind == "M":
                Hm, I, C = cfg.ssm_heads, cfg.ssm_inner, cfg.conv_width
                u = jax.random.uniform(next(keys), (2, Hm), jnp.float32)
                # step sizes log-uniform in [1e-3, 1e-1], stored through
                # the inverse of softplus; decay rates A in [1, 16]
                step = jnp.exp(u[0] * (math.log(0.1) - math.log(1e-3))
                               + math.log(1e-3))
                lp = {"ln": ones(D),
                      "w_in": mk((D, I + C + Hm), D),
                      "conv_w": _normal(next(keys), (C, cfg.conv_kernel),
                                        0.5, dt),
                      "conv_b": _normal(next(keys), (C,), 0.1, dt),
                      "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                      "a_log": jnp.log(1.0 + 15.0 * u[1]),
                      "d": jnp.ones((Hm,), jnp.float32),
                      "norm": ones(I), "w_out": mk((I, D), I, out)}
            elif kind == "R":
                H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
                # decays log-uniform in [0.9, 0.999], where trained gates
                # lie, stored through the inverse of the sigmoid
                u = jax.random.uniform(next(keys), (KV,), jnp.float32)
                g = jnp.exp(math.log(0.9) + u * (math.log(0.999)
                                                 - math.log(0.9)))
                lp = {"ln": ones(D), "wq": mk((D, H, Dh), D),
                      "wk": mk((D, KV, Dh), D), "wv": mk((D, KV, Dh), D),
                      "wg": mk((D, KV), D), "bg": jnp.log(g) - jnp.log1p(-g),
                      "qn": ones(Dh), "kn": ones(Dh),
                      "wo": mk((H, Dh, D), H * Dh, out)}
            elif kind == "F":
                F = cfg.mlp_ff
                lp = {"ln": ones(D), "w_gate": mk((D, F), D),
                      "w_up": mk((D, F), D), "w_down": mk((F, D), F, out)}
            elif kind == "L":
                H, Rq, Rkv = cfg.num_heads, cfg.q_rank, cfg.kv_rank
                dn, dr, dv = cfg.nope_dim, cfg.rope_dim, cfg.v_dim
                # (the rotary columns of w_uq and w_dkv are stored in the
                # rotate-half order; the keys' and values' up-projections
                # apart, as the absorbed form reads them)
                lp = {"ln": ones(D), "w_dq": mk((D, Rq), D),
                      "q_norm": ones(Rq), "w_uq": mk((Rq, H, dn + dr), Rq),
                      "w_dkv": mk((D, Rkv + dr), D), "kv_norm": ones(Rkv),
                      "w_uk": mk((Rkv, H, dn), Rkv),
                      "w_uv": mk((Rkv, H, dv), Rkv),
                      "wo": mk((H, dv, D), H * dv, out)}
            elif kind in "SW":
                H, Rq, Rkv, dn, dr, dv, _ = self._latent_sizes(kind == "W")
                lp = {"ln": ones(D), "w_dq": mk((D, Rq), D),
                      "q_norm": ones(Rq), "w_uq": mk((Rq, H, dn + dr), Rq),
                      "w_dkv": mk((D, Rkv + dr), D), "kv_norm": ones(Rkv),
                      "w_uk": mk((Rkv, H, dn), Rkv),
                      "w_uv": mk((Rkv, H, dv), Rkv),
                      "wo": mk((H, dv, D), H * dv, out)}
                lp["w_hg"] = mk((D, H), D)
                if kind == "S":
                    Hi, Di = cfg.index_heads, cfg.index_dim
                    lp.update(w_iq=mk((Rq, Hi, Di), Rq), w_ik=mk((D, Di), D),
                              ik_w=ones(Di), ik_b=jnp.zeros((Di,), dt),
                              w_iw=mk((D, Hi), D))
            elif kind == "B":
                H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
                # q and k norm weights of 2: at 1, rows of unit RMS score
                # with std 1 and a query over tens of thousands of tokens
                # spreads so evenly that no choice of blocks moves it (a
                # trained qk-norm sharpens attention)
                qk = jnp.full((Dh,), 2.0, dt)
                lp = {"ln": ones(D), "wq": mk((D, H, Dh), D),
                      "wk": mk((D, KV, Dh), D), "wv": mk((D, KV, Dh), D),
                      "qn": qk, "kn": qk, "w_hg": mk((D, H), D),
                      "wo": mk((H, Dh, D), H * Dh, out)}
            elif kind == "N":
                H, Dh = cfg.num_heads, cfg.head_dim
                lp = {"ln": ones(D), "wq": mk((D, H, Dh), D),
                      "wk": mk((D, H, Dh), D), "wv": mk((D, H, Dh), D),
                      "qn": ones(Dh), "kn": ones(Dh), "onorm": ones(H * Dh),
                      "wg": mk((D, H * Dh), D),
                      "wo": mk((H, Dh, D), H * Dh, out)}
            elif kind == "G":
                E, Eh = cfg.num_experts, cfg.experts_held
                F, Fs = cfg.expert_ff, cfg.shared_ff
                lp = {"ln": ones(D), "router": mk((D, E), D),
                      "select_bias": jax.random.normal(
                          next(keys), (E,), jnp.float32) * 0.01,
                      "w_gate": mk((Eh, D, F), D), "w_up": mk((Eh, D, F), D),
                      "w_down": mk((Eh, F, D), F, out),
                      "v_gate": mk((D, Fs), D), "v_up": mk((D, Fs), D),
                      "v_down": mk((Fs, D), Fs, out)}
            else:
                E, Eh, Z = cfg.num_experts, cfg.experts_held, cfg.moe_latent
                F, Fs = cfg.expert_ff, cfg.shared_ff
                # (a selection bias evens the experts' loads: a small
                # spread, so that it still decides near-ties and no
                # expert is preferred by much)
                lp = {"ln": ones(D), "router": mk((D, E), D),
                      "select_bias": jax.random.normal(
                          next(keys), (E,), jnp.float32) * 0.01,
                      "w_down": mk((D, Z), D), "w_up": mk((Z, D), Z, out),
                      "w1": mk((Eh, Z, F), Z), "w2": mk((Eh, F, Z), F),
                      "v1": mk((D, Fs), D), "v2": mk((Fs, D), Fs, out)}
            layers[name] = lp
        return {"embed": _normal(next(keys), (cfg.vocab_size, D),
                                 1.0 / cfg.scale_emb, dt),
                "head": mk((cfg.vocab_size, D), D),
                "final_norm": ones(D), "layers": layers}

    # ----------------------------------------------------------- attention
    def _qkv(self, x, lp):
        """RMSNorm + projections: x [B,S,D] -> q [B,H,S,Dh], k and v
        [B,KVH,S,Dh]; no rotary embedding, no bias."""
        with jax.named_scope("mx.qkv"):
            h = _norm(x, lp["ln"], self.cfg.eps)
            q, k, v = (jnp.einsum("bsd,dhe->bhse", h, lp[w],
                                  preferred_element_type=jnp.float32
                                  ).astype(x.dtype)
                       for w in ("wq", "wk", "wv"))
            return q, k, v

    def _attn_out(self, o, lp):
        with jax.named_scope("mx.attn_out"):
            return jnp.einsum("bhse,hed->bsd", o, lp["wo"],
                              preferred_element_type=jnp.float32
                              ).astype(o.dtype)

    def _attend(self, q, k, v):
        """Causal attention over a whole bucket; query head i reads K/V
        head ``i // (H // KVH)``."""
        rep = self.cfg.num_heads // self.cfg.num_kv_heads
        with jax.named_scope("mx.attention"):
            return _kernels.attention(q, jnp.repeat(k, rep, axis=1),
                                      jnp.repeat(v, rep, axis=1),
                                      causal=True)

    # ----------------------------------------------------- latent attention
    def _latent_sizes(self, window):
        """(heads, query rank, latent rank, un-rotated, rotary and value
        widths, rotary base) of ``L`` / ``S`` blocks, or of ``W`` ones."""
        cfg = self.cfg
        if window:
            return (cfg.swa_heads, cfg.swa_q_rank, cfg.swa_kv_rank,
                    cfg.swa_nope_dim, cfg.swa_rope_dim, cfg.swa_v_dim,
                    cfg.swa_rope_theta)
        return (cfg.num_heads, cfg.q_rank, cfg.kv_rank, cfg.nope_dim,
                cfg.rope_dim, cfg.v_dim, cfg.rope_theta)

    def _mla_parts(self, x, lp, positions, window=False, extras=False):
        """RMSNorm, the two low-rank paths with their norms, rotary at
        ``positions``: x [B,S,D], positions [B,S] -> q_nope [B,S,H,dn],
        q_rope [B,S,H,dr] (rotated), the normalised latent c [B,S,Rkv] and
        the rotated key k_rope [B,S,dr] that every head shares, in x's
        dtype.  ``[c | k_rope]`` is the row a token keeps.  ``window``: a
        ``W`` block's sizes; ``extras``: the normalised input and query
        latent [B,S,Rq] follow (the gate and the indexer read them)."""
        cfg = self.cfg
        _, _, Rkv, dn, _, _, theta = self._latent_sizes(window)
        f32 = jnp.float32
        n = _norm(x, lp["ln"], cfg.eps)
        cq = _norm(jnp.einsum("bsd,dr->bsr", n, lp["w_dq"],
                              preferred_element_type=f32).astype(x.dtype),
                   lp["q_norm"], cfg.eps)
        q = jnp.einsum("bsr,rhe->bshe", cq, lp["w_uq"],
                       preferred_element_type=f32).astype(x.dtype)
        ckr = jnp.einsum("bsd,dr->bsr", n, lp["w_dkv"],
                         preferred_element_type=f32).astype(x.dtype)
        c = _norm(ckr[..., :Rkv], lp["kv_norm"], cfg.eps)
        q_rope = _rope(q[..., dn:], positions, theta)
        k_rope = _rope(ckr[..., None, Rkv:], positions, theta)[:, :, 0]
        out = (q[..., :dn], q_rope, c, k_rope)
        return out + (n, cq) if extras else out

    def _mla_out(self, o, lp):
        """The heads' outputs [..., H, dv] through the output projection
        (``wo`` ``[H, dv, D]``: every kind whose heads are a leading axis)."""
        return jnp.einsum("...he,hed->...d", o, lp["wo"],
                          preferred_element_type=jnp.float32
                          ).astype(o.dtype)

    def _mla_sequence(self, x, lp):
        """A whole sequence in the EXPANDED form: every head's keys and
        values up-projected from the latent, the shared rotary key beside
        each head's own, one causal flash pass at query/key width ``dn +
        dr`` and value width ``dv``.  x [B,S,D] -> (out [B,S,D], the rows
        to keep [B,S,Rkv+dr])."""
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        with jax.named_scope("mx.mla_proj"):
            q_nope, q_rope, c, k_rope = self._mla_parts(x, lp, positions)
            q, k, v = self._mla_expand(q_nope, q_rope, c, k_rope, lp)
        with jax.named_scope("mx.attention"):
            o = _kernels.attention(q, k, v, causal=True,
                                   scale=self._mla_scale)
        with jax.named_scope("mx.mla_proj"):
            out = self._mla_out(jnp.transpose(o, (0, 2, 1, 3)), lp)
        return out, jnp.concatenate([c, k_rope], axis=-1)

    def _mla_expand(self, q_nope, q_rope, c, k_rope, lp):
        """The EXPANDED form's operands from :meth:`_mla_parts`' outputs:
        q, k ``[B,H,S,dn+dr]`` (each head's key up-projected from the
        latent, the shared rotary key beside it) and v ``[B,H,S,dv]``, in
        the queries' dtype."""
        f32, dt = jnp.float32, q_nope.dtype
        k_nope = jnp.einsum("bsr,rhe->bhse", c, lp["w_uk"],
                            preferred_element_type=f32).astype(dt)
        v = jnp.einsum("bsr,rhe->bhse", c, lp["w_uv"],
                       preferred_element_type=f32).astype(dt)
        q = jnp.transpose(jnp.concatenate([q_nope, q_rope], axis=-1),
                          (0, 2, 1, 3))
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, None], k_nope.shape[:3] + k_rope.shape[-1:])],
            axis=-1)
        return q, k, v

    def _mla_absorb(self, x, lp, positions):
        """One token a row, the ABSORBED form's first half: x [B,D],
        positions [B] -> (each head's query over a cache row [B, H,
        Rkv+dr]: the keys' up-projection folded in, ``q_nope W_uk^T``, a
        latent-wide query a head, beside its rotary part; the tokens' own
        rows to keep [B, Rkv+dr]), so the pages are read as they lie."""
        with jax.named_scope("mx.mla_proj"):
            q_nope, q_rope, c, k_rope = (a[:, 0] for a in self._mla_parts(
                x[:, None], lp, positions[:, None]))
            q_abs = jnp.einsum("bhe,rhe->bhr", q_nope, lp["w_uk"],
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)
            return jnp.concatenate([q_abs, q_rope], axis=-1), \
                jnp.concatenate([c, k_rope], axis=-1)

    def _mla_unabsorb(self, ctx, lp):
        """... and its second half: each head's attended latent [B, H,
        Rkv] through the values' up-projection and the output one."""
        with jax.named_scope("mx.mla_proj"):
            o = jnp.einsum("bhr,rhe->bhe", ctx, lp["w_uv"],
                           preferred_element_type=jnp.float32
                           ).astype(ctx.dtype)
            return self._mla_out(o, lp)

    def _write_latent_column(self, pool, a, page, slot, row, psz):
        """One token's row into its page of layer ``a`` of a pool whose
        pages hold their tokens on the lanes: the page is fetched, the
        column put in and the page put back whole, so nothing is scattered
        across a page's rows (a column scatter has the compiler turn the
        whole pool round, twice a step)."""
        with jax.named_scope("mx.kv_write"):
            at = jnp.minimum(page[:, 0], pool.shape[1] - 1)
            lane = jnp.arange(psz, dtype=jnp.int32)
            pages = jnp.where(
                lane[None, None, :] == slot[:, :, None],
                row.astype(pool.dtype)[:, :, None], pool[a, at])
            return pool.at[a, page[:, 0]].set(pages, mode="drop")

    # ------------------------------------ sparse and windowed latent blocks
    def _gate(self, o, n, lp):
        """``S`` / ``W``: each head's output o [..., H, dv] times one
        sigmoid of the block's normalised input n [..., D], before the
        output projection (``L`` blocks have no gate)."""
        with jax.named_scope("mx.attn_gate"):
            g = jax.nn.sigmoid(jnp.einsum("...d,dh->...h", n, lp["w_hg"],
                                          preferred_element_type=jnp.float32))
            return (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)

    def _index_parts(self, n, cq, lp, positions):
        """The indexer's inputs: n [B,S,D] (the block's normalised input),
        cq [B,S,Rq] (the normalised query latent), positions [B,S] ->
        index queries [B,S,Hi,Di] and keys [B,S,Di] in n's dtype (rotary on
        their first ``rope_dim`` dims; the key LayerNorm'd), and each
        query head's weight [B,S,Hi] float32, over sqrt(Hi) sqrt(Di): the
        score of key s for query t is ``sum_j w_tj relu(q_tj . k_s)``."""
        cfg = self.cfg
        f32 = jnp.float32
        r = cfg.rope_dim
        qi = jnp.einsum("bsr,rhe->bshe", cq, lp["w_iq"],
                        preferred_element_type=f32).astype(n.dtype)
        ki = jnp.einsum("bsd,de->bse", n, lp["w_ik"],
                        preferred_element_type=f32)
        mean = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
        ki = ((ki - mean) * lax.rsqrt(var + _INDEX_LN_EPS)
              * lp["ik_w"].astype(f32) + lp["ik_b"].astype(f32)
              ).astype(n.dtype)
        qi = jnp.concatenate([_rope(qi[..., :r], positions, cfg.rope_theta),
                              qi[..., r:]], axis=-1)
        ki = jnp.concatenate([_rope(ki[..., None, :r], positions,
                                    cfg.rope_theta)[:, :, 0], ki[..., r:]],
                             axis=-1)
        wi = jnp.einsum("bsd,dh->bsh", n, lp["w_iw"],
                        preferred_element_type=f32) \
            / math.sqrt(cfg.index_heads * cfg.index_dim)
        return qi, ki, wi

    def _dsa_sequence(self, x, lp):
        """``S`` over whole prompts: x [B,S,D] -> (out [B,S,D], the rows to
        keep [B,S,Rkv+dr+Di]: latent, rotary key, index key).  The indexer
        scores every key at or before each query and each query keeps the
        ``index_topk`` best (all of them while there are fewer), in chunks
        of :data:`_DSA_QUERY_CHUNK` queries (a chunk's index scores are
        what a step holds) run in :data:`_DSA_SEGMENTS` causal segments, a
        segment's against the keys before its end only (5/8 of the whole
        prompt's on average).  How the queries then attend over what they
        kept is ``kernels.sparse_prefill_route``'s to say: on the kernel's
        route each chunk emits its selection as an int8 mask over key
        positions, and one causal flash pass over the EXPANDED form
        (:meth:`_mla_expand`) attends where the mask holds; on the XLA
        twin's each chunk gathers its queries' selected rows and attends
        over them in the ABSORBED form (128 heads share one latent row, so
        the rows are gathered once for all heads)."""
        cfg = self.cfg
        B, S, _ = x.shape
        f32, dt = jnp.float32, x.dtype
        H, _, Rkv, dn, dr, dv, _ = self._latent_sizes(False)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        with jax.named_scope("mx.mla_proj"):
            q_nope, q_rope, c, k_rope, n, cq = self._mla_parts(
                x, lp, positions, extras=True)
        with jax.named_scope("mx.dsa_indexer"):
            qi, ki, wi = self._index_parts(n, cq, lp, positions)
        keys = jnp.concatenate([c, k_rope], axis=-1)          # [B,S,Rkv+dr]
        wide = functools.partial(jax.ShapeDtypeStruct, dtype=dt)
        masked = _kernels.sparse_prefill_route(
            wide((B, H, S, dn + dr)), wide((B, H, S, dn + dr)),
            wide((B, H, S, dv)),
            jax.ShapeDtypeStruct((B, S, S), jnp.int8)) is None
        Q = min(_DSA_QUERY_CHUNK, S)
        steps = -(-S // Q)
        pad = ((0, 0), (0, steps * Q - S))
        # (the twin's queries too: it attends a chunk at a time)
        qi, wi, *q_abs = (jnp.pad(a, pad + ((0, 0),) * (a.ndim - 2))
                          for a in (qi, wi) + (() if masked
                                               else (q_nope, q_rope)))

        def chunk(span, i):
            # queries [i Q, (i + 1) Q) against the keys before ``span``
            t0 = i * Q
            part = functools.partial(lax.dynamic_slice_in_dim, start_index=t0,
                                     slice_size=Q, axis=1)
            t = t0 + jnp.arange(Q, dtype=jnp.int32)
            with jax.named_scope("mx.dsa_indexer"):
                score = jnp.einsum("bqhd,bsd->bqhs", part(qi), ki[:, :span],
                                   preferred_element_type=f32)
                score = jnp.sum(jax.nn.relu(score) * part(wi)[..., None],
                                axis=2)                       # [B,Q,span]
            with jax.named_scope("mx.dsa_select"):
                score = jnp.where(jnp.arange(span)[None, None, :]
                                  <= t[None, :, None], score, -jnp.inf)
                top, idx = lax.top_k(score, min(cfg.index_topk, span))
                if masked:
                    return _selection_mask(score, top, idx, t, S)
                kept = idx <= t[None, :, None]                # [B,Q,K]
            with jax.named_scope("mx.sparse_attention"):
                rows = jax.vmap(lambda k, j: k[j])(keys[:, :span], idx)
                q = jnp.concatenate([jnp.einsum(
                    "bqhe,rhe->bqhr", part(q_abs[0]), lp["w_uk"],
                    preferred_element_type=f32).astype(dt), part(q_abs[1])],
                    axis=-1)
                s = jnp.einsum("bqhc,bqkc->bqhk", q, rows,
                               preferred_element_type=f32) * self._mla_scale
                s = jnp.where(kept[:, :, None, :], s, -1e30)
                e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
                ctx = jnp.einsum("bqhk,bqkr->bqhr", e.astype(dt),
                                 rows[..., :Rkv], preferred_element_type=f32)
                ctx = (ctx / jnp.sum(e, axis=-1)[..., None]).astype(dt)
            with jax.named_scope("mx.mla_proj"):
                return jnp.einsum("bqhr,rhe->bqhe", ctx, lp["w_uv"],
                                  preferred_element_type=f32).astype(dt)

        # causal segments: a later segment's chunks read more keys, an
        # earlier one's only those before its end (one loop a segment)
        per = -(-steps // _DSA_SEGMENTS)
        o = jnp.concatenate([
            lax.map(functools.partial(chunk, min(S, (c0 + per) * Q)),
                    jnp.arange(c0, min(c0 + per, steps), dtype=jnp.int32))
            for c0 in range(0, steps, per)])
        # the heads' outputs [B,S,H,dv], or the selection [B,S,S]
        o = jnp.moveaxis(o, 0, 1).reshape((B, steps * Q) + o.shape[3:])[:, :S]
        if masked:
            with jax.named_scope("mx.mla_proj"):
                q, k, v = self._mla_expand(q_nope, q_rope, c, k_rope, lp)
            with jax.named_scope("mx.sparse_attention"):
                o = jnp.transpose(_kernels.sparse_prefill_attention(
                    q, k, v, o, self._mla_scale), (0, 2, 1, 3))
        o = self._gate(o, n, lp)
        with jax.named_scope("mx.mla_proj"):
            out = self._mla_out(o, lp)
        return out, jnp.concatenate([keys, ki], axis=-1)

    def _swa_sequence(self, x, lp):
        """``W`` over whole prompts, in the EXPANDED form: x [B,S,D] ->
        (out [B,S,D], the rows a ring keeps [B,S,Rkv+dr]).  Position t sees
        t-window+1 .. t, so a chunk of ``Q >= window - 1`` queries needs
        only its own keys and the chunk's before it: banded causal
        attention, one chunk a step."""
        cfg = self.cfg
        B, S, _ = x.shape
        f32, dt = jnp.float32, x.dtype
        H, _, _, dn, dr, dv, _ = self._latent_sizes(True)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        with jax.named_scope("mx.mla_proj"):
            q_nope, q_rope, c, k_rope, n, _ = self._mla_parts(
                x, lp, positions, window=True, extras=True)
            k = jnp.concatenate([
                jnp.einsum("bsr,rhe->bshe", c, lp["w_uk"],
                           preferred_element_type=f32).astype(dt),
                jnp.broadcast_to(k_rope[:, :, None], (B, S, H, dr))],
                axis=-1)
            v = jnp.einsum("bsr,rhe->bshe", c, lp["w_uv"],
                           preferred_element_type=f32).astype(dt)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
        Q = -(-(cfg.window - 1) // 8) * 8
        steps = -(-S // Q)
        q, k, v = (jnp.pad(a, ((0, 0), (Q * (a is not q), steps * Q - S),
                               (0, 0), (0, 0))) for a in (q, k, v))

        def chunk(i):
            qc = lax.dynamic_slice_in_dim(q, i * Q, Q, axis=1)
            kc, vc = (lax.dynamic_slice_in_dim(a, i * Q, 2 * Q, axis=1)
                      for a in (k, v))
            t = i * Q + jnp.arange(Q, dtype=jnp.int32)[:, None]
            p = (i - 1) * Q + jnp.arange(2 * Q, dtype=jnp.int32)[None, :]
            seen = (p <= t) & (p > t - cfg.window) & (p >= 0)
            with jax.named_scope("mx.window_attention"):
                s = jnp.einsum("bqhe,bkhe->bhqk", qc, kc,
                               preferred_element_type=f32) * self._swa_scale
                s = jnp.where(seen, s, -1e30)
                e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
                o = jnp.einsum("bhqk,bkhe->bqhe", e.astype(dt), vc,
                               preferred_element_type=f32)
                return (o / jnp.moveaxis(jnp.sum(e, axis=-1), 1, 2)[..., None]
                        ).astype(dt)

        o = lax.map(chunk, jnp.arange(steps, dtype=jnp.int32))
        o = jnp.moveaxis(o, 0, 1).reshape(B, steps * Q, H, dv)[:, :S]
        o = self._gate(o, n, lp)
        with jax.named_scope("mx.mla_proj"):
            out = self._mla_out(o, lp)
        return out, jnp.concatenate([c, k_rope], axis=-1)

    def _ring_of(self, rows, lengths):
        """A ``W`` block's ring after a prompt: rows [B,S,w] (lengths [B] or
        None: all S) -> [B, w, ring], column j holding the latest real
        position p with ``p % ring == j`` (zeros where there is none)."""
        B, S, _ = rows.shape
        last = (jnp.full((B,), S, jnp.int32) if lengths is None
                else lengths.astype(jnp.int32))[:, None] - 1
        lane = jnp.arange(self.cfg.ring, dtype=jnp.int32)[None, :]
        p = last - (last - lane) % self.cfg.ring                 # [B, ring]
        got = jnp.take_along_axis(rows, jnp.clip(p, 0, S - 1)[..., None],
                                  axis=1)
        return jnp.swapaxes(jnp.where((p >= 0)[..., None], got, 0), 1, 2)

    def _latent_absorb(self, x, lp, positions, window=False):
        """One token a row of an ``S`` or ``W`` block: x [B,D] -> (each
        head's absorbed query beside its rotary part [B,H,Rkv+dr], the row
        to keep [B,Rkv+dr], the normalised input [B,D], the query latent
        [B,Rq])."""
        with jax.named_scope("mx.mla_proj"):
            q_nope, q_rope, c, k_rope, n, cq = (
                a[:, 0] for a in self._mla_parts(
                    x[:, None], lp, positions[:, None], window=window,
                    extras=True))
            q_abs = jnp.einsum("bhe,rhe->bhr", q_nope, lp["w_uk"],
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)
            return jnp.concatenate([q_abs, q_rope], axis=-1), \
                jnp.concatenate([c, k_rope], axis=-1), n, cq

    def _latent_finish(self, ctx, n, lp):
        """Attended latents [B,H,Rkv] through the values' up-projection,
        the gate and the output projection."""
        with jax.named_scope("mx.mla_proj"):
            o = jnp.einsum("bhr,rhe->bhe", ctx, lp["w_uv"],
                           preferred_element_type=jnp.float32
                           ).astype(ctx.dtype)
        o = self._gate(o, n, lp)
        with jax.named_scope("mx.mla_proj"):
            return self._mla_out(o, lp)

    def _dsa_step(self, x, lp, positions, pool, a, page, slot, page_table,
                  psz):
        """``S``, one token a row: its row (latent, rotary key, index key)
        goes into its page; the indexer scores every token the row's pages
        hold, the ``index_topk`` best are kept (ties to the earlier
        position, as ``lax.top_k`` breaks them) and the absorbed query
        attends over those alone (``kernels.sparse_latent_attention``).
        Returns (out [B,D], the pool, each row's tokens scored and tokens
        attended [2, B] int32)."""
        cfg = self.cfg
        B = x.shape[0]
        q, row, n, cq = self._latent_absorb(x, lp, positions)
        with jax.named_scope("mx.dsa_indexer"):
            qi, ki, wi = (t[:, 0] for t in self._index_parts(
                n[:, None], cq[:, None], lp, positions[:, None]))
        pool = self._write_latent_column(
            pool, a, page, slot, jnp.concatenate([row, ki], axis=-1), psz)
        lengths = positions + 1
        with jax.named_scope("mx.dsa_indexer"):
            score = _kernels.index_scores(
                qi, wi, pool, page_table, lengths,
                cfg.kv_rank + cfg.rope_dim, layer=a).reshape(B, -1)
        with jax.named_scope("mx.dsa_select"):
            held = jnp.arange(score.shape[1], dtype=jnp.int32)[None, :] \
                < lengths[:, None]
            _, idx = lax.top_k(jnp.where(held, score, -jnp.inf),
                               min(cfg.index_topk, score.shape[1]))
            chosen = jnp.zeros(score.shape, jnp.int32).at[
                jnp.arange(B)[:, None], idx].set(1) * held
            counts = jnp.stack([jnp.sum(held, axis=1, dtype=jnp.int32),
                                jnp.sum(chosen, axis=1)])
            chosen = chosen.reshape(page_table.shape + (psz,))
        ctx = _kernels.sparse_latent_attention(
            q, pool, page_table, lengths, chosen, self._mla_scale,
            cfg.kv_rank, layer=a)
        return self._latent_finish(ctx, n, lp), pool, counts

    def _swa_step(self, x, lp, positions, ring):
        """``W``, one token a row: its row goes into column ``position %
        ring`` of the slot's ring [B, Rkv+dr, ring] (every column rewritten
        in place: the ring is donated), then the absorbed query attends
        over the columns whose positions lie in the window.  Returns (out
        [B,D], the ring, each row's columns attended [1, B] int32)."""
        cfg = self.cfg
        Rkv = cfg.swa_kv_rank
        q, row, n, _ = self._latent_absorb(x, lp, positions, window=True)
        R = ring.shape[-1]
        lane = jnp.arange(R, dtype=jnp.int32)[None, :]
        with jax.named_scope("mx.kv_write"):
            ring = jnp.where((lane == (positions % R)[:, None])[:, None, :],
                             row.astype(ring.dtype)[:, :, None], ring)
        with jax.named_scope("mx.window_attention"):
            p = positions[:, None] - (positions[:, None] - lane) % R
            seen = (p >= 0) & (p > positions[:, None] - cfg.window)
            s = jnp.einsum("bhc,bcp->bhp", q, ring,
                           preferred_element_type=jnp.float32) \
                * self._swa_scale
            s = jnp.where(seen[:, None, :], s, -1e30)
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            ctx = jnp.einsum("bhp,bcp->bhc", e.astype(ring.dtype),
                             ring[:, :Rkv], preferred_element_type=jnp.float32)
            ctx = (ctx / jnp.sum(e, axis=-1)[..., None]).astype(x.dtype)
        return self._latent_finish(ctx, n, lp), ring, \
            jnp.sum(seen, axis=1, dtype=jnp.int32)[None]

    # ------------------------------------------ block-sparse attention (B)
    def _block_qkv(self, x, lp):
        """RMSNorm, the projections and the per-head q / k norms: x
        [B,S,D] -> (n [B,S,D], q [B,S,H,Dh], k and v [B,S,KVH,Dh]) in x's
        dtype; no positional encoding."""
        cfg = self.cfg
        with jax.named_scope("mx.qkv"):
            n = _norm(x, lp["ln"], cfg.eps)
            q, k, v = (jnp.einsum("bsd,dhe->bshe", n, lp[w],
                                  preferred_element_type=jnp.float32
                                  ).astype(x.dtype)
                       for w in ("wq", "wk", "wv"))
            return n, _norm(q, lp["qn"], cfg.eps), \
                _norm(k, lp["kn"], cfg.eps), v

    def _block_out(self, o, n, lp):
        """The heads' outputs [..., H, Dh], gated a head, through the
        output projection."""
        o = self._gate(o, n, lp)
        with jax.named_scope("mx.attn_out"):
            return self._mla_out(o, lp)

    def _block_scores(self, q, ckeys, t, blocks):
        """Each query's score of every block per K/V head: q [B,T,KVH,G,Dh]
        (the G query heads of each K/V head), ckeys [B,KVH,NC,Dh] (row j the
        mean key of positions ``j stride .. j stride + kernel - 1``), t
        [B,T] the queries' positions -> [B,KVH,T,blocks] float32: the
        softmax of ``q . c / sqrt(Dh)`` over the compressed keys complete
        at or before t, summed over the group, the largest over the kernels
        a block overlaps (-inf where none is complete)."""
        cfg = self.cfg
        st, per = cfg.kernel_stride, cfg.block_size // cfg.kernel_stride
        ks = cfg.kernel_size // cfg.kernel_stride
        nc = ckeys.shape[2]
        s = jnp.einsum("btghe,bgje->bgthj", q, ckeys,
                       preferred_element_type=jnp.float32) \
            / math.sqrt(cfg.head_dim)
        done = jnp.arange(nc, dtype=jnp.int32) * st + cfg.kernel_size - 1 \
            <= t[:, None, :, None, None]                  # [B,1,T,1,NC]
        s = jnp.where(done, s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0))
        r = jnp.sum(e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True),
                                    1e-30), axis=3)       # [B,KVH,T,NC]
        r = jnp.where(done[:, :, :, 0], r, -jnp.inf)
        # block b's kernels are j in [b per - ks + 1, b per + per - 1]
        want = blocks * per + per
        r = jnp.pad(r, ((0, 0),) * 3 + ((ks - 1, max(0, want - nc)),),
                    constant_values=-jnp.inf)
        return functools.reduce(jnp.maximum, [
            r[..., o:o + blocks * per:per] for o in range(per + ks - 1)])

    def _block_choose(self, score, t):
        """The blocks a query keeps: score [..., blocks] (the query's, at
        position t [...]) -> bool [..., blocks].  Every block at or before
        t's own while t is under ``dense_len``; else the first
        ``init_blocks``, the blocks of the ``local_window`` latest
        positions, and the highest-scoring of the rest (ties to the lower
        block) up to ``block_topk`` in all."""
        cfg = self.cfg
        nb = score.shape[-1]
        b = jnp.arange(nb, dtype=jnp.int32)
        t = t[..., None]
        held = b * cfg.block_size <= t
        forced = held & ((b < cfg.init_blocks) | (
            (b + 1) * cfg.block_size > t - cfg.local_window + 1))
        k = min(cfg.block_topk, nb)
        rest = jnp.where(held & ~forced, score, -jnp.inf)
        top, idx = lax.top_k(rest, k)
        room = cfg.block_topk - jnp.sum(forced, axis=-1, keepdims=True)
        # the room-th best of the rest, and the last tied block it kept
        at = jnp.clip(room - 1, 0, k - 1)
        kth = jnp.take_along_axis(top, at, axis=-1)
        rank = jnp.arange(k, dtype=jnp.int32)
        last = jnp.max(jnp.where((top == kth) & (rank < room), idx, -1),
                       axis=-1, keepdims=True)
        scored = held & ~forced & (room > 0) & (
            (rest > kth) | ((rest == kth) & (b <= last)))
        return jnp.where(t < cfg.dense_len, held, forced | scored)

    def _compressed(self, k, lengths):
        """A prompt's compressed keys and the running sums a decode step
        carries on: k [B,S,KVH,Dh] (S whole strides), lengths [B] or None
        -> (ckeys [B,KVH,NC,Dh] in k's dtype, the state's max_len /
        stride rows, zeros past the prompt's complete kernels; sums
        [B,ks,KVH,Dh] float32: the last ``ks - 1`` whole strides' key sums
        before the one the next position falls in, then that one's so
        far)."""
        cfg = self.cfg
        B, S, KV, Dh = k.shape
        st, ks = cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride
        f32 = jnp.float32
        if lengths is not None:
            real = jnp.arange(S)[None, :] < lengths[:, None]
            k = jnp.where(real[..., None, None], k, 0)
        part = jnp.sum(k.astype(f32).reshape(B, S // st, st, KV, Dh), axis=2)
        n = S // st - ks + 1
        ck = sum(part[:, o:o + n] for o in range(ks)) / cfg.kernel_size
        if lengths is not None:
            done = jnp.arange(n) * st + cfg.kernel_size <= lengths[:, None]
            ck = jnp.where(done[..., None, None], ck, 0.0)
        nc = cfg.max_len // st
        ck = jnp.pad(jnp.moveaxis(ck, 1, 2).astype(k.dtype),
                     ((0, 0), (0, 0), (0, nc - n), (0, 0)))
        m = (jnp.full((B,), S, jnp.int32) if lengths is None
             else lengths.astype(jnp.int32)) // st
        pad = jnp.pad(part, ((0, 0), (ks - 1, 1), (0, 0), (0, 0)))
        sums = jnp.take_along_axis(
            pad, (m[:, None] + jnp.arange(ks))[..., None, None], axis=1)
        return ck, sums

    def _block_sequence(self, x, lp, lengths=None):
        """``B`` over whole prompts: x [B,S,D] -> (out [B,S,D], k and v
        [B,KVH,S,Dh] for the pages, the compressed keys [B,KVH,NC,Dh] and
        running sums for the state).  The selection runs a chunk of
        :data:`_BLOCK_QUERY_CHUNK` queries a step, each a mask over the
        prompt's blocks per K/V head; one causal flash pass a prompt then
        attends where the mask holds (``kernels.block_sparse_prefill``)."""
        cfg = self.cfg
        B, S, _ = x.shape
        H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        G = H // KV
        n, q, k, v = self._block_qkv(x, lp)
        with jax.named_scope("mx.compressed_key_write"):
            ck, sums = self._compressed(k, lengths)
        nb = -(-S // cfg.block_size)
        Q = min(_BLOCK_QUERY_CHUNK, S)
        steps = -(-S // Q)
        qg = jnp.pad(q, ((0, 0), (0, steps * Q - S), (0, 0), (0, 0))
                     ).reshape(B, steps * Q, KV, G, Dh)
        # (only the compressed keys a prompt of this bucket can complete)
        used = ck[:, :, :S // cfg.kernel_stride]

        def chunk(i):
            t = i * Q + jnp.arange(Q, dtype=jnp.int32)
            tb = jnp.broadcast_to(t, (B, Q))
            score = self._block_scores(
                lax.dynamic_slice_in_dim(qg, i * Q, Q, axis=1), used, tb, nb)
            keep = self._block_choose(score, tb[:, None])
            return keep.astype(jnp.bfloat16)

        with jax.named_scope("mx.block_select"):
            mask = lax.map(chunk, jnp.arange(steps, dtype=jnp.int32))
            # [steps, B, KVH, Q, nb] -> [B KVH, S, nb]
            mask = jnp.moveaxis(mask, 0, 2).reshape(
                B * KV, steps * Q, nb)[:, :S]
        qt, kt, vt = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v))
        with jax.named_scope("mx.block_sparse_prefill"):
            o = _kernels.block_sparse_prefill(
                qt, kt, vt, mask, 1.0 / math.sqrt(Dh), cfg.block_size)
        out = self._block_out(jnp.transpose(o, (0, 2, 1, 3)), n, lp)
        return out, kt, vt, ck, sums

    def _block_step(self, x, lp, positions, kv, a, name, page, slot,
                    page_table):
        """``B``, one token a row: its K and V go into their page, its key
        into the running sums (and, where it completes a kernel, the
        kernel's mean into the compressed keys); the compressed keys score
        the row's blocks, each K/V head keeps its blocks and the group's
        query heads attend over those pages alone, the partial current one
        last (``kernels.block_sparse_attention``).  Returns (out [B,D], the
        cache arrays it rewrote by name, [compressed keys scored, tokens
        attended a query head] [2, B] int32)."""
        cfg = self.cfg
        B = x.shape[0]
        H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        G, psz = H // KV, cfg.block_size
        st, ks = cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride
        n, q, k, v = (t[:, 0] for t in self._block_qkv(x[:, None], lp))
        out = {}
        with jax.named_scope("mx.kv_write"):
            # the page is fetched, the row put in and the page put back
            # whole: a scatter of one row across a page's heads has the
            # compiler turn the whole pool round, twice a step
            at = jnp.minimum(page[:, 0], kv["k"].shape[1] - 1)
            row = jnp.arange(psz, dtype=jnp.int32)[None, None, :, None] \
                == slot[:, :1, None, None]
            for key, val in (("k", k), ("v", v)):
                pages = jnp.where(row, val.astype(kv[key].dtype)[:, :, None],
                                  kv[key][a, at])
                out[key] = kv[key].at[a, page[:, 0]].set(pages, mode="drop")
        with jax.named_scope("mx.compressed_key_write"):
            sums, ck = kv["cks" + name], kv["ck" + name]
            cur = sums[:, -1] + k.astype(jnp.float32)
            whole = (positions % st == st - 1)[:, None, None, None]
            j = (positions - cfg.kernel_size + 1) // st
            mean = (jnp.sum(sums[:, :-1], axis=1) + cur) / cfg.kernel_size
            at = jnp.where((positions % st == st - 1) & (j >= 0), j,
                           ck.shape[2])
            out["ck" + name] = ck.at[jnp.arange(B), :, at].set(
                mean.astype(ck.dtype), mode="drop")
            out["cks" + name] = jnp.where(
                whole, jnp.concatenate(
                    [sums[:, 1:-1], cur[:, None], jnp.zeros_like(cur)[:, None]]
                    if ks > 1 else [jnp.zeros_like(cur)[:, None]], axis=1),
                jnp.concatenate([sums[:, :-1], cur[:, None]], axis=1))
        W = page_table.shape[1]
        with jax.named_scope("mx.block_select"):
            used = out["ck" + name][:, :, :W * psz // st]
            score = self._block_scores(q.reshape(B, 1, KV, G, Dh), used,
                                       positions[:, None], W)[:, :, 0]
            keep = self._block_choose(score, positions[:, None])  # [B,KVH,W]
            # the kept blocks in order (the current one, the highest, last)
            width = min(W, max(cfg.block_topk, -(-cfg.dense_len // psz),
                               cfg.init_blocks + cfg.local_window // psz + 1))
            blk = jnp.arange(W, dtype=jnp.int32)
            _, order = lax.top_k(jnp.where(keep, -blk, -W - blk), width)
            kept = jnp.sum(keep, axis=-1, dtype=jnp.int32)        # [B,KVH]
            pages = jnp.take_along_axis(page_table[:, None, :], order, axis=2)
            pages = jnp.minimum(pages, kv["k"].shape[1] - 1) * KV \
                + jnp.arange(KV, dtype=jnp.int32)[None, :, None]
            lengths = (kept - 1) * psz + (positions % psz + 1)[:, None]
            done = jnp.clip((positions - cfg.kernel_size + 1) // st + 1, 0,
                            used.shape[2])
        pool = [kv_.reshape((kv_.shape[0], kv_.shape[1] * KV)
                            + kv_.shape[3:]) for kv_ in (out["k"], out["v"])]
        with jax.named_scope("mx.block_sparse_attention"):
            o = _kernels.block_sparse_attention(
                q.reshape(B * KV, G, 1, Dh), pool[0], pool[1],
                pages.reshape(B * KV, width), lengths.reshape(-1),
                1.0 / math.sqrt(Dh), layer=a)
        res = self._block_out(o.reshape(B, H, Dh), n, lp)
        return res, out, jnp.stack(
            [done, jnp.sum(lengths, axis=1, dtype=jnp.int32) // KV])

    # ------------------------------------------------ lightning attention (N)
    def _lit_parts(self, x, lp, positions):
        """RMSNorm, the projections, SiLU, per-head RMSNorm of q and k and
        rotary at ``positions``: x [B,S,D] -> (n [B,S,D], q over sqrt(Dh),
        k and v [B,S,H,Dh] in x's dtype)."""
        cfg = self.cfg
        with jax.named_scope("mx.qkv"):
            n = _norm(x, lp["ln"], cfg.eps)
            q, k, v = (jax.nn.silu(jnp.einsum(
                "bsd,dhe->bshe", n, lp[w],
                preferred_element_type=jnp.float32)).astype(x.dtype)
                for w in ("wq", "wk", "wv"))
        with jax.named_scope("mx.rope"):
            q = _rope(_norm(q, lp["qn"], cfg.eps), positions, cfg.rope_theta)
            k = _rope(_norm(k, lp["kn"], cfg.eps), positions, cfg.rope_theta)
        return n, q, k, v

    def _lit_out(self, o, n, lp):
        """The heads' outputs o [..., H, Dh] (float32): RMSNorm over their
        concatenation, the elementwise sigmoid gate of the block's
        normalised input n, the output projection."""
        cfg = self.cfg
        dt = n.dtype
        with jax.named_scope("mx.out_gate"):
            flat = o.reshape(o.shape[:-2] + (-1,))
            flat = flat * lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1,
                                             keepdims=True) + cfg.eps)
            g = jax.nn.sigmoid(jnp.einsum("...d,de->...e", n, lp["wg"],
                                          preferred_element_type=jnp.float32))
            y = (flat.astype(dt) * lp["onorm"]).astype(jnp.float32) * g
            y = y.astype(dt).reshape(o.shape)
        with jax.named_scope("mx.attn_out"):
            return self._mla_out(y, lp)

    def _lit_sequence(self, x, lp, name, lengths=None):
        """``N`` over whole prompts from the zero state, in chunks: x
        [B,S,D] -> (out [B,S,D], final state [B,H,Dh,Dh] float32).  A
        position at or past ``lengths`` has a zero key and no decay: the
        state leaves it as it came."""
        cfg = self.cfg
        B, S, _ = x.shape
        Q = min(cfg.chunk, S)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        n, q, k, v = self._lit_parts(x, lp, positions)
        with jax.named_scope("mx.lightning_scan"):
            logd = jnp.broadcast_to(self._lit_log_decay[name],
                                    (B, S, cfg.num_heads))
            if lengths is not None:
                real = positions < lengths[:, None]
                k = jnp.where(real[..., None, None], k, 0)
                logd = jnp.where(real[..., None], logd, 0.0)
            pad = -S % Q
            if pad:
                q, k, v, logd = (
                    jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                    for a in (q, k, v, logd))
            y, state = _lightning_scan(q, k, v, logd, Q)
            y = y[:, :S] / math.sqrt(cfg.head_dim)
        return self._lit_out(y, n, lp), state

    def _lit_step(self, x, lp, name, positions, state):
        """One token a row: x [B,D], state [B,H,Dh,Dh] float32 -> (out
        [B,D], new state): ``S <- lambda S + k^T v`` and ``q S`` in float32
        through ``kernels.retention_update`` (its expanded key and query
        the key and query themselves, its normaliser a zero it drops)."""
        cfg = self.cfg
        B = x.shape[0]
        n, q, k, v = (a[:, 0] for a in self._lit_parts(
            x[:, None], lp, positions[:, None]))
        f32 = jnp.float32
        with jax.named_scope("mx.lightning_update"):
            decay = jnp.broadcast_to(jnp.exp(self._lit_log_decay[name]),
                                     (B, cfg.num_heads))
            state, _, num, _ = _kernels.retention_update(
                state, jnp.zeros(state.shape[:3], f32), k.astype(f32),
                q.astype(f32)[:, :, None] / math.sqrt(cfg.head_dim), decay,
                v)
        return self._lit_out(num[:, :, 0], n, lp), state

    # -------------------------------------------------------------- Mamba-2
    def _ssm_split(self, x, lp):
        """RMSNorm + the input projection: x [..., D] -> z [..., I], the
        stream to convolve [..., C], step logits [..., Hm]."""
        cfg = self.cfg
        h = _norm(x, lp["ln"], cfg.eps)
        zxd = jnp.einsum("...d,de->...e", h, lp["w_in"],
                         preferred_element_type=jnp.float32).astype(x.dtype)
        I, C = cfg.ssm_inner, cfg.conv_width
        return zxd[..., :I], zxd[..., I:I + C], zxd[..., I + C:]

    def _ssm_parts(self, xbc, dt, lp):
        """The convolved, activated stream and step logits -> X
        [..., G, R, P], B and C [..., G, N], steps and log-decays
        [..., G, R] in float32 (head ``g*R + r`` uses group g)."""
        cfg = self.cfg
        G, N, P = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_head_dim
        R = cfg.ssm_heads // G
        I = cfg.ssm_inner
        lead = xbc.shape[:-1]
        X = xbc[..., :I].reshape(lead + (G, R, P))
        Bm = xbc[..., I:I + G * N].reshape(lead + (G, N))
        Cm = xbc[..., I + G * N:].reshape(lead + (G, N))
        step = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        step = step.reshape(lead + (G, R))
        rate = -jnp.exp(lp["a_log"]).reshape(G, R)
        return X, Bm, Cm, step, rate

    def _ssm_finish(self, Y, X, z, lp):
        """Skip term, gate, grouped RMSNorm (one group per B/C group) and
        the output projection.  Y, X [..., G, R, P]; z [..., I]."""
        cfg = self.cfg
        G = cfg.ssm_groups
        lead = z.shape[:-1]
        Y = Y + lp["d"].reshape(G, -1)[..., None] * X.astype(jnp.float32)
        y = Y.reshape(lead + (G, -1)) * jax.nn.silu(
            z.astype(jnp.float32)).reshape(lead + (G, -1))
        y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + cfg.eps)
        y = y.reshape(lead + (-1,)).astype(z.dtype) * lp["norm"]
        return jnp.einsum("...i,id->...d", y, lp["w_out"],
                          preferred_element_type=jnp.float32
                          ).astype(z.dtype)

    def _conv(self, window, lp):
        """Depthwise causal convolution + SiLU at the last column of each
        ``conv_kernel``-wide window: window [..., K, C] -> [..., C]."""
        acc = jnp.einsum("...kc,ck->...c", window.astype(jnp.float32),
                         lp["conv_w"].astype(jnp.float32))
        return jax.nn.silu(acc + lp["conv_b"].astype(jnp.float32)
                           ).astype(window.dtype)

    def _ssm_sequence(self, x, lp, lengths=None):
        """A whole sequence from the zero state, in the block (SSD) form:
        x [B,S,D] -> (out [B,S,D], final state [B,G,R,P,N] f32, conv tail
        [B,K-1,C]: the last K-1 pre-activation columns).  Positions at or
        past ``lengths`` take a zero step, so they neither decay nor feed
        the state, and the tail ends at the last real column."""
        cfg = self.cfg
        S = x.shape[1]
        K, Q = cfg.conv_kernel, min(cfg.chunk, S)
        with jax.named_scope("mx.ssm_conv"):
            z, xbc, dt = self._ssm_split(x, lp)
            padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
            window = jnp.stack([padded[:, j:j + S] for j in range(K)],
                               axis=2)                      # [B,S,K,C]
            if lengths is None:
                tail = padded[:, S:]
            else:
                # padded column lengths+j is position lengths-(K-1)+j
                take = lengths[:, None] + jnp.arange(K - 1)[None, :]
                tail = jnp.take_along_axis(padded, take[..., None], axis=1)
            conv = self._conv(window, lp)
        with jax.named_scope("mx.ssm_scan"):
            X, Bm, Cm, step, rate = self._ssm_parts(conv, dt, lp)
            if lengths is not None:
                real = jnp.arange(S)[None, :] < lengths[:, None]
                step = jnp.where(real[..., None, None], step, 0.0)
            pad = -S % Q
            if pad:
                X, Bm, Cm, step = (
                    jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                    for a in (X, Bm, Cm, step))
            Y, state = _ssd(X, Bm, Cm, step, rate, Q)
            Y, X = Y[:, :S], X[:, :S]
        out = self._ssm_finish(Y, X, z, lp)
        return out, state, tail

    def _ssm_step(self, x, lp, state, tail):
        """One token a row: x [B,D], state [B,G,R,P,N] f32, tail
        [B,K-1,C] -> (out [B,D], new state, new tail)."""
        with jax.named_scope("mx.ssm_conv"):
            z, xbc, dt = self._ssm_split(x, lp)
            window = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)],
                                     axis=1)
            conv = self._conv(window, lp)
        with jax.named_scope("mx.ssm_update"):
            X, Bm, Cm, step, rate = self._ssm_parts(conv, dt, lp)
            decay = jnp.exp(step * rate)                     # [B,G,R]
            xd = X.astype(jnp.float32) * step[..., None]     # [B,G,R,P]
            state = decay[..., None, None] * state \
                + xd[..., None] * Bm.astype(jnp.float32)[:, :, None, None]
            Y = jnp.sum(state * Cm.astype(jnp.float32)[:, :, None, None],
                        axis=-1)                             # [B,G,R,P]
        return self._ssm_finish(Y, X, z, lp), state, window[:, 1:]

    # ------------------------------------------------------ power retention
    def _ret_parts(self, x, lp, positions):
        """RMSNorm + projections, per-head RMSNorm of q and k, rotary at
        ``positions``: x [B,S,D], positions [B,S] -> q [B,S,KVH,R,Dh]
        (query head ``j*R + r`` reads K/V head j), k and v [B,S,KVH,Dh] in
        x's dtype, log-decays [B,S,KVH] float32 (one gate a K/V head)."""
        cfg = self.cfg
        KV, Dh = cfg.num_kv_heads, cfg.head_dim
        f32 = jnp.float32
        with jax.named_scope("mx.qkv"):
            h = _norm(x, lp["ln"], cfg.eps)
            q, k, v = (jnp.einsum("bsd,dhe->bshe", h, lp[w],
                                  preferred_element_type=f32).astype(x.dtype)
                       for w in ("wq", "wk", "wv"))
            logg = jax.nn.log_sigmoid(
                jnp.einsum("bsd,dh->bsh", h, lp["wg"],
                           preferred_element_type=f32) + lp["bg"])
        with jax.named_scope("mx.rope"):
            q = _rope(_norm(q, lp["qn"], cfg.eps), positions, cfg.rope_theta)
            k = _rope(_norm(k, lp["kn"], cfg.eps), positions, cfg.rope_theta)
        return q.reshape(q.shape[:2] + (KV, -1, Dh)), k, v, logg

    def _ret_out(self, y, lp):
        """The read-outs [..., KVH, R, Dh] through the output projection."""
        with jax.named_scope("mx.attn_out"):
            y = y.reshape(y.shape[:-3] + (-1, y.shape[-1]))
            return jnp.einsum("...he,hed->...d", y, lp["wo"],
                              preferred_element_type=jnp.float32
                              ).astype(y.dtype)

    def _ret_sequence(self, x, lp, lengths=None):
        """A whole sequence from the zero state, in chunks: x [B,S,D] ->
        (out [B,S,D], final state [B,KVH,N,Dh] f32, final normaliser
        [B,KVH,N] f32).  Positions at or past ``lengths`` have a zero key
        and a gate of one: they neither feed nor decay the state."""
        cfg = self.cfg
        B, S, _ = x.shape
        Q = min(cfg.chunk, S)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        q, k, v, logg = self._ret_parts(x, lp, positions)
        with jax.named_scope("mx.retention_scan"):
            if lengths is not None:
                real = positions < lengths[:, None]
                k = jnp.where(real[..., None, None], k, 0)
                logg = jnp.where(real[..., None], logg, 0.0)
            pad = -S % Q
            if pad:
                q, k, v, logg = (
                    jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                    for a in (q, k, v, logg))
            y, state, z = _retention_scan(q, k, v, logg, Q)
            y = y[:, :S].astype(x.dtype)
        return self._ret_out(y, lp), state, z

    def _ret_step(self, x, lp, positions, state, z):
        """One token a row: x [B,D], positions [B], state [B,KVH,N,Dh] and
        z [B,KVH,N] f32 -> (out [B,D], new state, new z): ``S <- g S +
        phi(k) v^T`` in float32, then the query heads of each K/V head
        read the new state, the state not rounded on the way
        (``kernels.retention_update``: one Pallas pass over the state, or
        its XLA twin)."""
        q, k, v, logg = (a[:, 0] for a in self._ret_parts(
            x[:, None], lp, positions[:, None]))
        with jax.named_scope("mx.retention_update"):
            pq, pk = _phi(q), _phi(k)            # [B,KVH,R,N], [B,KVH,N]
            state, z, num, den = _kernels.retention_update(
                state, z, pk, pq, jnp.exp(logg), v)
            y = (num / den[..., None]).astype(x.dtype)   # [B,KVH,R,Dh]
        return self._ret_out(y, lp), state, z

    # ------------------------------------------------------------ gated MLP
    def _mlp(self, x, lp):
        """``W_down(silu(W_gate m) * (W_up m))``, m = RMSNorm(x)."""
        with jax.named_scope("mx.mlp"):
            h = _norm(x, lp["ln"], self.cfg.eps)

            def dot(a, w):
                return jnp.einsum("...d,df->...f", a, w,
                                  preferred_element_type=jnp.float32)

            mid = jax.nn.silu(dot(h, lp["w_gate"])) * dot(h, lp["w_up"])
            return dot(mid.astype(x.dtype), lp["w_down"]).astype(x.dtype)

    # ------------------------------------------------- mixture of experts
    def _moe(self, x, lp, rows_valid=None):
        """x [T, D] -> (out [T, D], the held experts' routing stats, the
        experts each row chose [T, top_k] int32)."""
        cfg = self.cfg
        dt = x.dtype

        def dot(a, w):
            return jnp.einsum("td,df->tf", a, w,
                              preferred_element_type=jnp.float32)

        h = _norm(x, lp["ln"], cfg.eps)
        with jax.named_scope("mx.moe_router"):
            experts, weights = _moe.sigmoid_top_k(
                h, lp["router"], lp["select_bias"], cfg.top_k,
                cfg.route_scale)
        u = dot(h, lp["w_down"]).astype(dt)
        with jax.named_scope("mx.moe_experts"):
            y, stats = _moe.dropless_experts(
                u, experts, weights, lp["w1"], lp["w2"],
                expert_offset=cfg.expert_offset, rows_valid=rows_valid)
        out = dot(y.astype(dt), lp["w_up"])
        with jax.named_scope("mx.moe_shared"):
            out = out + dot(_relu2(dot(h, lp["v1"])).astype(dt), lp["v2"])
        return out.astype(dt), stats, experts

    def _gated_moe(self, x, lp, rows_valid=None):
        """``G``: x [T, D] -> (out [T, D], the held experts' routing stats,
        the experts each row chose [T, top_k] int32).  The routed experts
        and the shared one are gated and read the block's normalised input
        at full width."""
        cfg = self.cfg
        dt = x.dtype

        def dot(a, w):
            return jnp.einsum("td,df->tf", a, w,
                              preferred_element_type=jnp.float32)

        h = _norm(x, lp["ln"], cfg.eps)
        with jax.named_scope("mx.moe_router"):
            experts, weights = _moe.sigmoid_top_k(
                h, lp["router"], lp["select_bias"], cfg.top_k,
                cfg.route_scale)
        with jax.named_scope("mx.moe_experts"):
            out, stats = _moe.dropless_experts(
                h, experts, weights, lp["w_up"], lp["w_down"],
                expert_offset=cfg.expert_offset, rows_valid=rows_valid,
                w_gate=lp["w_gate"])
        with jax.named_scope("mx.moe_shared"):
            mid = jax.nn.silu(dot(h, lp["v_gate"])) * dot(h, lp["v_up"])
            out = out + dot(mid.astype(dt), lp["v_down"])
        return out.astype(dt), stats, experts

    # -------------------------------------------------------------- forward
    def apply(self, params, tokens):
        """tokens [B, S] int32 -> logits [B, S, V] (fp32): the whole
        sequence from empty state, no cache."""
        x = self._embed(params, tokens)
        x, _, _, _ = self._run(params, x)
        x = self._final(params, x)
        return jnp.einsum("bsd,vd->bsv", x, params["head"],
                          preferred_element_type=jnp.float32)

    def _embed(self, params, tokens):
        """The tokens' rows of the embedding, times ``scale_emb``."""
        x = params["embed"][tokens].astype(self.cfg.dtype)
        if self.cfg.scale_emb != 1.0:
            x = (x.astype(jnp.float32) * self.cfg.scale_emb).astype(x.dtype)
        return x

    def _add(self, x, out):
        """A block's output into the residual stream, times
        ``residual_scale``."""
        if self.cfg.residual_scale == 1.0:
            return x + out
        return x + (out.astype(jnp.float32) * self.cfg.residual_scale
                    ).astype(x.dtype)

    def _final(self, params, x):
        """The final norm, times ``logit_scale``: what the head reads."""
        x = _norm(x, params["final_norm"], self.cfg.eps)
        if self.cfg.logit_scale != 1.0:
            x = (x.astype(jnp.float32) * self.cfg.logit_scale).astype(x.dtype)
        return x

    def _run(self, params, x, lengths=None, kv_sink=None):
        """The blocks over whole sequences x [B,S,D].  Returns (x, the
        final per-sequence state of the ``M`` and ``R`` blocks by its name
        in the cache (:meth:`kv_spec`), summed expert stats, the experts
        chosen [E and G blocks, B, S, top_k]); ``kv_sink(block, k, v)``
        sees every attention block's K/V, and every latent block's rows
        (``kv_sink(block, rows)``)."""
        B, S, D = x.shape
        valid = None if lengths is None else \
            (jnp.arange(S)[None, :] < lengths[:, None]).reshape(-1)
        states, stats, routed = {}, [], []
        for name, kind in zip(self.names, self.kinds):
            lp = params["layers"][name]
            if kind == "*":
                q, k, v = self._qkv(x, lp)
                if kv_sink is not None:
                    kv_sink(name, k, v)
                x = self._add(x, self._attn_out(self._attend(q, k, v), lp))
            elif kind == "M":
                with jax.named_scope("mx.ssm"):
                    out, states["ssm" + name], states["conv" + name] = \
                        self._ssm_sequence(x, lp, lengths)
                    x = self._add(x, out)
            elif kind == "R":
                with jax.named_scope("mx.retention"):
                    out, states["ret" + name], states["retz" + name] = \
                        self._ret_sequence(x, lp, lengths)
                    x = self._add(x, out)
            elif kind == "F":
                x = self._add(x, self._mlp(x, lp))
            elif kind in "LS":
                out, rows = (self._mla_sequence if kind == "L"
                             else self._dsa_sequence)(x, lp)
                if kv_sink is not None:
                    kv_sink(name, rows)
                x = self._add(x, out)
            elif kind == "W":
                out, rows = self._swa_sequence(x, lp)
                states["ring" + name] = self._ring_of(rows, lengths)
                x = self._add(x, out)
            elif kind == "B":
                out, k, v, states["ck" + name], states["cks" + name] = \
                    self._block_sequence(x, lp, lengths)
                if kv_sink is not None:
                    kv_sink(name, k, v)
                x = self._add(x, out)
            elif kind == "N":
                with jax.named_scope("mx.lightning"):
                    out, states["lit" + name] = self._lit_sequence(
                        x, lp, name, lengths)
                    x = self._add(x, out)
            else:
                with jax.named_scope("mx.moe"):
                    mix = self._moe if kind == "E" else self._gated_moe
                    # a pair's widest float32 row: the experts' inner
                    # width, or what they return (D for G, E's latent)
                    wide = max(self.cfg.expert_ff, D if kind == "G"
                               else self.cfg.moe_latent)
                    out, st, chosen = _in_row_chunks(
                        mix, x.reshape(B * S, D), lp, valid,
                        self.cfg.top_k * wide * 4)
                    stats.append(st)
                    routed.append(chosen.reshape(B, S, -1))
                    x = self._add(x, out.reshape(B, S, D))
        return x, states, _sum_stats(stats), _stack_routed(routed, (B, S))

    # --------------------------------------- generation (pages and state)
    def kv_spec(self, quantized=False):
        """The cache this model keeps, for ``deploy.export_generation``:
        K/V pages of the layers that attend (``row_width`` = K/V heads x
        head size) and, under ``state``, the arrays every decode slot
        holds a row of — per ``M`` block the float32 recurrent state and
        the convolution tail, per ``R`` block the float32 retention state
        (``ret_width`` = head_dim (head_dim + 1) / 2 rows a K/V head: the
        exact upper triangle of the key's square, row-major, off-diagonal
        entries weighted sqrt 2) and its normaliser — as ``{"name",
        "shape" (of one slot's row), "dtype"}``; an array is ``[slots,
        *shape]``.  ``num_layers`` counts the layers that attend: 0 is a
        model that keeps no page.  A model of ``L`` blocks keeps LATENT
        pages: ``pools`` names its one pool (``kv``; K and V pools ``k``
        and ``v`` where the key is absent), ``row_width`` is the latent's
        rank + the rotary key's width of which the first ``value_width``
        are the values, and ``page_layout`` ``"lanes"`` says a page is
        ``[row_width, page_size]``, its tokens on the lanes (absent: a page
        is ``[page_size, row_width]``).  A model of ``S`` blocks keeps the
        index keys below the rows (``index_rows`` ``[first, end)``) and
        says how many blocks select and how many tokens each keeps
        (``sparse``); one of ``W`` blocks keeps a ring a block in ``state``
        (``ring<block>`` ``[rows, columns]``) and says how many blocks, their
        window and their columns (``rings``).  A model of ``B`` blocks keeps
        K and V pages a head apart (``page_layout`` ``"heads"``: a page is
        ``[num_heads, page_size, head_dim]``), per block in ``state`` its
        compressed keys (``ck<block>`` ``[KVH, max_len / kernel_stride,
        Dh]``) and running key sums (``cks<block>`` ``[kernel_size /
        kernel_stride, KVH, Dh]`` float32), and says what the selection
        keeps (``block_sparse``); one of ``N`` blocks keeps a float32
        ``lit<block>`` ``[H, Dh, Dh]`` state a block."""
        if quantized:
            raise ValueError("HybridLM keeps no int8 K/V pages")
        cfg = self.cfg
        if "*" in self.kinds and self.latent:
            raise ValueError(
                "pattern %r mixes '*' (K and V pages) and 'L' (latent "
                "pages): a model keeps one kind of page, and kv_spec "
                "describes one" % (cfg.pattern,))
        G, R = cfg.ssm_groups, cfg.ssm_heads // cfg.ssm_groups
        KV, N = cfg.num_kv_heads, cfg.ret_width
        state = []
        for name, kind in zip(self.names, self.kinds):
            if kind == "M":
                state.append({"name": "ssm" + name, "dtype": "float32",
                              "shape": [G, R, cfg.ssm_head_dim,
                                        cfg.ssm_state]})
                state.append({"name": "conv" + name,
                              "dtype": jnp.dtype(cfg.dtype).name,
                              "shape": [cfg.conv_kernel - 1,
                                        cfg.conv_width]})
            elif kind == "R":
                state.append({"name": "ret" + name, "dtype": "float32",
                              "shape": [KV, N, cfg.head_dim]})
                state.append({"name": "retz" + name, "dtype": "float32",
                              "shape": [KV, N]})
            elif kind == "W":
                state.append({"name": "ring" + name,
                              "dtype": jnp.dtype(cfg.dtype).name,
                              "shape": [cfg.swa_kv_rank + cfg.swa_rope_dim,
                                        cfg.ring]})
            elif kind == "B":
                state.append({"name": "ck" + name,
                              "dtype": jnp.dtype(cfg.dtype).name,
                              "shape": [KV, cfg.max_len // cfg.kernel_stride,
                                        cfg.head_dim]})
                state.append({"name": "cks" + name, "dtype": "float32",
                              "shape": [cfg.kernel_size // cfg.kernel_stride,
                                        KV, cfg.head_dim]})
            elif kind == "N":
                state.append({"name": "lit" + name, "dtype": "float32",
                              "shape": [cfg.num_heads, cfg.head_dim,
                                        cfg.head_dim]})
        spec = {"num_layers": len(self.attn_index),
                "num_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
                "row_width": cfg.num_kv_heads * cfg.head_dim,
                "dtype": jnp.dtype(cfg.dtype).name, "state": state}
        if self.latent:
            width = cfg.kv_rank + cfg.rope_dim
            spec.update(num_heads=1, head_dim=width, row_width=width,
                        value_width=cfg.kv_rank, pools=["kv"],
                        page_layout="lanes")
        if self.sparse:
            wide = width + cfg.index_dim
            spec.update(head_dim=wide, row_width=wide,
                        index_rows=[width, wide],
                        sparse={"layers": self.kinds.count("S"),
                                "top_k": cfg.index_topk})
        if "W" in self.kinds:
            spec["rings"] = {"layers": self.kinds.count("W"),
                             "window": cfg.window, "columns": cfg.ring}
        if self.block_sparse:
            spec.update(page_layout="heads", block_sparse={
                "layers": self.kinds.count("B"), "block": cfg.block_size,
                "top_k": cfg.block_topk})
        return spec

    def init_kv_pages(self, num_pages, page_size, slots=1):
        """Zeroed cache, as :meth:`kv_spec` describes it: ``k`` / ``v``
        ``[attention layers, num_pages, page_size, KVH*Dh]`` or the one
        latent pool ``kv`` ``[L layers, num_pages, kv_rank + rope_dim,
        page_size]``, and every state array ``[slots, ...]``."""
        from ..deploy import _kv_pool_specs, kv_pool_names
        spec = dict(self.kv_spec(), page_size=int(page_size))
        return {name: jnp.zeros(s.shape, s.dtype) for name, s in zip(
            kv_pool_names(spec),
            _kv_pool_specs(spec, int(num_pages), int(slots)))}

    def _logits_last(self, params, x):
        with jax.named_scope("mx.lm_head"):
            x = self._final(params, x)
            return jnp.einsum("bd,vd->bv", x, params["head"],
                              preferred_element_type=jnp.float32)

    # readout, sampling and the cache-free oracle are TransformerLM's own
    _sample_last = TransformerLM._sample_last
    _choose = TransformerLM._choose
    greedy_decode = TransformerLM.greedy_decode

    def prefill(self, params, kv, tokens, lengths, page_table, page_size,
                sample=None, return_logits=False, slots=None,
                return_replay=False):
        """Whole prompts: tokens [B,S] (padded past ``lengths``), page_table
        [B,W].  Every attention block's K/V goes into the pages as in
        ``TransformerLM.prefill`` (every latent block's rows, and every
        ``B`` block's K and V, a whole page at a time: the expanded K and V
        are never kept); every ``M``, ``R`` and ``N`` block runs from
        the zero state in its chunked form and leaves the prompt's final
        state (and convolution tail, or normaliser) in row ``slots[b]``
        (default: row b) of its state arrays, whatever that row held, as
        every ``B`` block leaves its compressed keys and running sums.
        Padded positions take no step and route to no expert.  Returns ``(new_kv, next_token [B])``;
        with ``return_replay`` (:attr:`replay`) two more come last: the
        experts every position chose ``[E blocks, B, S, top_k]`` int32 and
        the next token's log-probability ``[B]`` float32."""
        B, S = tokens.shape
        psz = int(page_size)
        pool = kv["kv" if self.latent else "k"].shape[1]
        iota = jnp.arange(S, dtype=jnp.int32)
        pages = jnp.where(iota[None, :] < lengths[:, None],
                          page_table[:, iota // psz], pool)
        offs = jnp.broadcast_to(iota % psz, (B, S))
        nkv = dict(kv)

        def sink(name, k, v=None):
            with jax.named_scope("mx.kv_write"):
                a = self.attn_index[name]
                if self.block_sparse:
                    # K and V [B,KVH,S,Dh] go in a page (a block) at a
                    # time, each head's rows apart: [KVH, psz, Dh] a page
                    n = -(-S // psz)
                    ids = jnp.where(
                        jnp.arange(n)[None, :] * psz < lengths[:, None],
                        page_table[:, :n], pool)
                    for key, val in (("k", k), ("v", v)):
                        rows = jnp.pad(val, ((0, 0), (0, 0),
                                             (0, n * psz - S), (0, 0)))
                        rows = jnp.moveaxis(rows.reshape(
                            B, val.shape[1], n, psz, -1), 2, 1)
                        nkv[key] = nkv[key].at[a, ids].set(
                            rows.astype(nkv[key].dtype), mode="drop")
                    return
                if v is None:
                    # latent rows [B,S,width] go in a page at a time, its
                    # tokens onto the lanes; a page with no real token
                    # (or one the table withholds) is dropped, a last
                    # page's tail is rows no length reaches
                    n = -(-S // psz)
                    rows = jnp.pad(k, ((0, 0), (0, n * psz - S), (0, 0)))
                    rows = jnp.transpose(rows.reshape(B, n, psz, -1),
                                         (0, 1, 3, 2))
                    ids = jnp.where(
                        jnp.arange(n)[None, :] * psz < lengths[:, None],
                        page_table[:, :n], pool)
                    nkv["kv"] = nkv["kv"].at[a, ids].set(
                        rows.astype(nkv["kv"].dtype), mode="drop")
                    return
                for key, val in (("k", k), ("v", v)):
                    rows = jnp.transpose(val, (0, 2, 1, 3)).reshape(B, S, -1)
                    nkv[key] = nkv[key].at[a, pages, offs].set(
                        rows.astype(nkv[key].dtype), mode="drop")

        x = self._embed(params, tokens)
        x, states, _, routed = self._run(params, x, lengths, sink)
        rows = jnp.arange(B, dtype=jnp.int32) if slots is None else slots
        for key, state in states.items():
            nkv[key] = kv[key].at[rows].set(state.astype(kv[key].dtype))
        last = jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        ids, logits = self._sample_last(params, last, lengths, sample)
        out = (nkv, ids, logits) if return_logits else (nkv, ids)
        return out + (routed, _logprob(logits, ids)) if return_replay \
            else out

    def decode_step(self, params, kv, token_ids, positions, page_table,
                    page_size, sample=None, return_logits=False,
                    return_stats=False, return_replay=False):
        """One token for every decode slot: row b of the batch IS slot b
        of the state arrays.  Attention blocks append K/V and attend
        through ``kernels.paged_attention`` (handed the whole pool and the
        layer's index), latent blocks append a row and attend in the
        absorbed form through ``kernels.latent_paged_attention``; ``M`` and
        ``R`` blocks advance their state in
        float32, ``R`` blocks rotating q and k by ``positions``.  A
        slot with no request (position 0, as the engine passes it) routes
        to no expert; its state row is advanced like any other and is
        overwritten by the next prefill into it.  With ``return_stats``
        the int32 sums over the ``E`` blocks of :attr:`decode_stats` come
        next: pairs computed on held experts, distinct held experts hit,
        the largest load on one expert, then, summed over the rows with a
        request, the tokens the ``S`` blocks' indexer scored and the tokens
        their selection kept, the ring columns the ``W`` blocks attended,
        and the compressed keys the ``B`` blocks scored and the tokens a
        query head of theirs attended (where the pattern has them); ``N``
        blocks advance their float32 state through
        ``kernels.retention_update``; with ``return_replay`` the experts
        every row chose ``[E blocks, B, top_k]`` int32 and the produced
        tokens' log-probabilities ``[B]`` float32 come last."""
        B = token_ids.shape[0]
        psz = int(page_size)
        page = jnp.take_along_axis(page_table, (positions // psz)[:, None],
                                   axis=1)
        slot = (positions % psz)[:, None]
        active = positions > 0
        x = self._embed(params, token_ids)                      # [B,D]
        nkv = dict(kv)
        stats, routed = [], []
        counts = {"S": [], "W": [], "B": []}
        for name, kind in zip(self.names, self.kinds):
            lp = params["layers"][name]
            if kind == "*":
                a = self.attn_index[name]
                q, k, v = self._qkv(x[:, None], lp)
                with jax.named_scope("mx.kv_write"):
                    for key, val in (("k", k), ("v", v)):
                        row = jnp.transpose(val, (0, 2, 1, 3)) \
                            .reshape(B, 1, -1)
                        nkv[key] = nkv[key].at[a, page, slot].set(
                            row.astype(nkv[key].dtype), mode="drop")
                o = _kernels.paged_attention(q, nkv["k"], nkv["v"],
                                             page_table, positions + 1,
                                             layer=a)
                x = self._add(x, self._attn_out(o, lp)[:, 0])
            elif kind == "M":
                with jax.named_scope("mx.ssm"):
                    out, nkv["ssm" + name], tail = self._ssm_step(
                        x, lp, kv["ssm" + name], kv["conv" + name])
                    nkv["conv" + name] = tail
                    x = self._add(x, out)
            elif kind == "R":
                with jax.named_scope("mx.retention"):
                    out, nkv["ret" + name], nkv["retz" + name] = \
                        self._ret_step(x, lp, positions, kv["ret" + name],
                                       kv["retz" + name])
                    x = self._add(x, out)
            elif kind == "F":
                x = self._add(x, self._mlp(x, lp))
            elif kind == "L":
                a = self.attn_index[name]
                q, row = self._mla_absorb(x, lp, positions)
                # a row is a COLUMN of its page (tokens lie on the lanes)
                nkv["kv"] = self._write_latent_column(nkv["kv"], a, page,
                                                      slot, row, psz)
                ctx = _kernels.latent_paged_attention(
                    q, nkv["kv"], page_table, positions + 1,
                    self._mla_scale, self.cfg.kv_rank, layer=a)
                x = self._add(x, self._mla_unabsorb(ctx, lp))
            elif kind == "S":
                out, nkv["kv"], got = self._dsa_step(
                    x, lp, positions, nkv["kv"], self.attn_index[name], page,
                    slot, page_table, psz)
                counts["S"].append(got)
                x = self._add(x, out)
            elif kind == "W":
                out, nkv["ring" + name], got = self._swa_step(
                    x, lp, positions, kv["ring" + name])
                counts["W"].append(got)
                x = self._add(x, out)
            elif kind == "B":
                out, wrote, got = self._block_step(
                    x, lp, positions, nkv, self.attn_index[name], name, page,
                    slot, page_table)
                nkv.update(wrote)
                counts["B"].append(got)
                x = self._add(x, out)
            elif kind == "N":
                with jax.named_scope("mx.lightning"):
                    out, nkv["lit" + name] = self._lit_step(
                        x, lp, name, positions, kv["lit" + name])
                    x = self._add(x, out)
            else:
                with jax.named_scope("mx.moe"):
                    mix = self._moe if kind == "E" else self._gated_moe
                    out, st, chosen = mix(x, lp, active)
                    stats.append(st)
                    routed.append(chosen)
                    x = self._add(x, out)
        ids, logits = self._sample_last(params, x, positions + 1, sample)
        out = (nkv, ids)
        if return_logits:
            out += (logits,)
        if return_stats:
            st = _sum_stats(stats)
            # the attention blocks' counts over the rows that hold a request
            seen = [jnp.sum(jnp.where(active, sum(c), 0), axis=1)
                    for c in counts.values() if c]
            st = jnp.stack([st[n] for n in _STATS])
            out += (jnp.concatenate([st] + seen) if seen else st,)
        if return_replay:
            out += (_stack_routed(routed, (B,)), _logprob(logits, ids))
        return out


def _selection_mask(score, top, idx, t, length):
    """The set ``lax.top_k`` kept, as an int8 mask over key positions, with
    no scatter: score [B,Q,span] (-inf past each query's position t [Q]),
    top / idx [B,Q,K] its top-k values and positions -> [B,Q,length],
    zeros past ``span``.  A key is kept where it scores above the k-th
    value, or equals it at or before the last tied position the top-k kept
    (its ties go to the lower position), and lies at or before t."""
    span = score.shape[-1]
    kth = top[..., -1:]
    last = jnp.max(jnp.where(top == kth, idx, -1), axis=-1, keepdims=True)
    p = jnp.arange(span, dtype=jnp.int32)
    keep = ((score > kth) | ((score == kth) & (p <= last))) \
        & (p[None, :] <= t[:, None])
    return jnp.pad(keep.astype(jnp.int8),
                   ((0, 0), (0, 0), (0, length - span)))


def _in_row_chunks(mix, x, lp, valid, row_bytes):
    """A mixture of experts over token rows x [T, D] (``valid`` [T] or
    None), where a token row's pairs make ``row_bytes`` of float32 products
    (its experts x the widest product row: 2.5 GB a product at 16,384
    tokens x 8 of a 5,120-wide model): whole where the T rows' products fit
    in ``_MOE_PAIR_BYTES``, else a chunk of the most rows that fit, a power
    of two, a step.  A chunked layer's counts are the chunks' (pairs
    summed, the others their largest)."""
    T, D = x.shape
    if T * row_bytes <= _MOE_PAIR_BYTES:
        return mix(x, lp, valid)
    size = 1 << (_MOE_PAIR_BYTES // row_bytes).bit_length() - 1
    n = -(-T // size)
    live = jnp.arange(n * size) < T
    if valid is not None:
        live = live & jnp.pad(valid, (0, n * size - T))
    rows = jnp.pad(x, ((0, n * size - T), (0, 0)))
    out, st, chosen = lax.map(lambda a: mix(a[0], lp, a[1]), (
        rows.reshape(n, size, D), live.reshape(n, size)))
    st = {k: jnp.sum(v) if k == "pairs" else jnp.max(v)
          for k, v in st.items()}
    return out.reshape(-1, D)[:T], st, \
        chosen.reshape((-1,) + chosen.shape[2:])[:T]


def _logprob(logits, ids):
    """log softmax(logits)[ids], float32: logits [B, V], ids [B]."""
    chosen = jnp.take_along_axis(logits, ids[:, None], axis=-1)[:, 0]
    return chosen - jax.nn.logsumexp(logits, axis=-1)


def _stack_routed(routed, lead):
    return jnp.stack(routed) if routed \
        else jnp.zeros((0,) + lead + (0,), jnp.int32)


def _sum_stats(stats):
    zero = jnp.zeros((), jnp.int32)
    return {n: sum((s[n] for s in stats), zero) for n in _STATS}


def _rope(x, positions, theta):
    """Rotary positions over the whole head, rotate-half: x [B,S,heads,Dh],
    positions [B,S] -> x's shape and dtype, rotated in float32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[..., None, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


@functools.lru_cache(maxsize=None)
def _triangle(Dh):
    """The upper triangle ``a <= b`` of a Dh x Dh square in row-major
    order: the one-hot picks of a and of b ``[Dh, N]`` bool, and the
    entries' weights ``[N]`` float32 (1 on the diagonal, sqrt 2 off it)."""
    ia, ib = np.triu_indices(Dh)
    rows = np.arange(Dh)[:, None]
    return rows == ia[None, :], rows == ib[None, :], \
        np.where(ia == ib, 1.0, math.sqrt(2.0)).astype(np.float32)


def _phi(u, dtype=jnp.float32):
    """The symmetric square of the last axis, u [..., Dh] -> [..., Dh (Dh +
    1) / 2] in ``dtype``: ``u_a u_b`` over the upper triangle
    (:func:`_triangle`), off-diagonal entries times sqrt 2, so that ``phi(q)
    . phi(k) = (q . k)^2`` exactly.  The two factors are picked by one-hot
    products (exact in u's own dtype: one term a sum), which the MXU does
    and a gather along the minor axis does not; their product is float32,
    rounded once where ``dtype`` is narrower."""
    first, second, weight = _triangle(u.shape[-1])
    a, b = (jnp.einsum("...e,en->...n", u, pick.astype(u.dtype)
                       ).astype(jnp.float32) for pick in (first, second))
    return (a * b * weight).astype(dtype)


def _retention_scan(q, k, v, logg, Q):
    """Power retention of degree 2 from an empty state, in chunks of ``Q``:
    ``S_t = g_t S_{t-1} + phi(k_t) v_t^T``, ``z_t = g_t z_{t-1} +
    phi(k_t)``, ``y_t = phi(q_t)^T S_t / phi(q_t)^T z_t``.  Inside a chunk
    the masked, decayed ``(q k^T)^2`` product (no expansion); between
    chunks the state and its normaliser, carried in float32 by a scan that
    expands one chunk's ``phi`` at a time.  ``_ssd``'s recurrence with ``B
    = phi(k)``, ``C = phi(q)``, written beside it: the in-chunk product is
    another, several query heads read one state, and every chunk's
    ``phi(q)`` at once would be gigabytes.

    q [B,S,KVH,R,Dh]; k, v [B,S,KVH,Dh]; logg [B,S,KVH] f32 (<= 0; 0 with
    a zero key where nothing is to happen).  S a multiple of Q.  Returns
    ``(y [B,S,KVH,R,Dh] f32, final state [B,KVH,N,Dh] f32, final
    normaliser [B,KVH,N] f32)``."""
    B, S, KV, R, Dh = q.shape
    c = S // Q
    f32 = jnp.float32
    # the query side rides as [B, c, KVH, Q, R, ...]: a K/V head's Q x R
    # queries are then the rows of one plain batched product
    qc = jnp.moveaxis(q.reshape(B, c, Q, KV, R, Dh), 3, 2)
    kc = k.reshape(B, c, Q, KV, Dh)
    vc = v.reshape(B, c, Q, KV, Dh)
    cum = jnp.moveaxis(jnp.cumsum(logg.reshape(B, c, Q, KV), axis=2), -1, 2)
    # inside a chunk: position i hears j <= i, decayed by what lies between
    qk = jnp.einsum("bchire,bcjhe->bchirj", qc, kc,
                    preferred_element_type=f32)
    seg = cum[..., :, None] - cum[..., None, :]              # [B,c,KVH,i,j]
    low = jnp.tril(jnp.ones((Q, Q), bool))
    mix = (jnp.square(qk) * jnp.exp(jnp.where(low, seg, -jnp.inf)
                                    )[:, :, :, :, None]).astype(v.dtype)
    num = jnp.einsum("bchirj,bcjhe->bchire", mix, vc,
                     preferred_element_type=f32)
    den = jnp.sum(mix.astype(f32), axis=-1)                  # [B,c,KVH,i,R]

    def chunk(carry, xs):
        state, z = carry
        qx, kx, vx, into, to_end, whole = xs
        # (phi(q) in the activations' dtype, 169 MB a chunk in float32 at
        # the published widths: the MXU would round it on the way in, and
        # what a prefill costs is the bytes of these expansions)
        pq = _phi(qx.reshape(B, KV, Q * R, Dh), qx.dtype)    # [B,KVH,QR,N]
        pk = _phi(kx) * to_end[..., None]                    # [B,Q,KVH,N]
        # what the state this chunk enters with adds at each position
        n_in = jnp.einsum("bhmn,bhne->bhme", pq, state.astype(qx.dtype),
                          preferred_element_type=f32)
        d_in = jnp.sum(pq * z[:, :, None], axis=-1)
        # ... and what the chunk adds to the state by its end
        state = whole[..., None, None] * state + jnp.einsum(
            "bjhn,bjhe->bhne", pk.astype(vx.dtype), vx,
            preferred_element_type=f32)
        z = whole[..., None] * z + jnp.sum(pk, axis=1)
        return (state, z), (
            n_in.reshape(B, KV, Q, R, Dh) * into[..., None, None],
            d_in.reshape(B, KV, Q, R) * into[..., None])

    N = Dh * (Dh + 1) // 2
    (state, z), (n_in, d_in) = lax.scan(
        chunk, (jnp.zeros((B, KV, N, Dh), f32), jnp.zeros((B, KV, N), f32)),
        tuple(jnp.moveaxis(a, 1, 0) for a in (
            qc, kc, vc, jnp.exp(cum),
            jnp.moveaxis(jnp.exp(cum[..., -1:] - cum), 2, -1),
            jnp.exp(cum[..., -1]))))
    y = (num + jnp.moveaxis(n_in, 0, 1)) \
        / (den + jnp.moveaxis(d_in, 0, 1))[..., None]
    return jnp.moveaxis(y, 2, 3).reshape(B, S, KV, R, Dh), state, z


def _ssd(X, Bm, Cm, step, rate, Q):
    """The recurrence ``S_t = exp(step_t rate) S_{t-1} + step_t X_t (x)
    B_t``, ``Y_t = S_t . C_t`` from ``S = 0``, in chunks of ``Q`` (the
    state-space-duality block form): inside a chunk a masked, decayed
    ``(C B^T) X`` product; between chunks the state, carried in float32.

    X [B,S,G,R,P]; Bm, Cm [B,S,G,N]; step [B,S,G,R] f32 (0 where nothing
    is to happen); rate [G,R] f32 (< 0).  S a multiple of Q.  Returns
    ``(Y [B,S,G,R,P] f32, final state [B,G,R,P,N] f32)``."""
    B, S, G, R, P = X.shape
    N = Bm.shape[-1]
    c = S // Q
    f32 = jnp.float32
    xd = (X.astype(f32) * step[..., None]).astype(X.dtype) \
        .reshape(B, c, Q, G, R, P)
    Bc = Bm.reshape(B, c, Q, G, N)
    Cc = Cm.reshape(B, c, Q, G, N)
    cum = jnp.cumsum((step * rate).reshape(B, c, Q, G, R), axis=2)
    # inside a chunk: position i hears j <= i, decayed by what lies between
    cb = jnp.einsum("bcign,bcjgn->bcijg", Cc, Bc,
                    preferred_element_type=f32)
    seg = cum[:, :, :, None] - cum[:, :, None, :]           # [B,c,i,j,G,R]
    low = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    mix = cb[..., None] * jnp.exp(jnp.where(low, seg, -jnp.inf))
    Y = jnp.einsum("bcijgr,bcjgrp->bcigrp", mix.astype(X.dtype), xd,
                   preferred_element_type=f32)
    # what each chunk adds to the state by its end
    to_end = jnp.exp(cum[:, :, -1:] - cum)                  # [B,c,Q,G,R]
    grown = jnp.einsum("bcjgn,bcjgrp->bcgrpn", Bc,
                       (xd.astype(f32) * to_end[..., None]).astype(X.dtype),
                       preferred_element_type=f32)
    whole = jnp.exp(cum[:, :, -1])                          # [B,c,G,R]

    def carry(state, xs):
        g, w = xs
        return w[..., None, None] * state + g, state

    state, entering = lax.scan(
        carry, jnp.zeros((B, G, R, P, N), f32),
        (jnp.moveaxis(grown, 1, 0), jnp.moveaxis(whole, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                 # [B,c,G,R,P,N]
    Y = Y + jnp.einsum("bcign,bcgrpn->bcigrp", Cc.astype(f32), entering,
                       preferred_element_type=f32) \
        * jnp.exp(cum)[..., None]
    return Y.reshape(B, S, G, R, P), state


def _lightning_log_decay(heads, layer, layers):
    """An ``N`` block's log-decays [heads] float32: ``-s_h (1 - l / (L -
    1) + 1e-5)``, ``s_h = 2^(-8 (h + 1) / heads)`` (the ALiBi slopes), ``l``
    the block's published layer of ``L``."""
    slope = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return (-slope * (1.0 - layer / max(layers - 1, 1) + 1e-5)).astype(
        np.float32)


def _lightning_scan(q, k, v, logd, Q):
    """Linear attention under a per-head decay from an empty state, in
    chunks of ``Q``: ``S_t = exp(logd_t) S_{t-1} + k_t^T v_t``, ``y_t = q_t
    S_t``.  Inside a chunk the masked, decayed ``q k^T`` product; between
    chunks the state, carried in float32 by a scan whose products keep
    float32 (``Precision.HIGHEST``: the state is not rounded on the way).

    q, k, v [B,S,H,Dh]; logd [B,S,H] f32 (<= 0; 0 with a zero key where
    nothing is to happen).  S a multiple of Q.  Returns ``(y [B,S,H,Dh]
    f32, final state [B,H,Dh,Dh] f32)``."""
    B, S, H, Dh = q.shape
    c = S // Q
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    qc, kc, vc = (a.reshape(B, c, Q, H, Dh) for a in (q, k, v))
    cum = jnp.cumsum(logd.reshape(B, c, Q, H), axis=2)          # [B,c,Q,H]
    # inside a chunk: position i hears j <= i, decayed by what lies between
    seg = jnp.moveaxis(cum, 3, 2)                                # [B,c,H,Q]
    low = jnp.tril(jnp.ones((Q, Q), bool))
    mix = jnp.einsum("bcihe,bcjhe->bchij", qc, kc,
                     preferred_element_type=f32) * jnp.exp(jnp.where(
                         low, seg[..., :, None] - seg[..., None, :], -jnp.inf))
    y = jnp.einsum("bchij,bcjhe->bcihe", mix.astype(v.dtype), vc,
                   preferred_element_type=f32)

    def chunk(state, xs):
        qx, kx, vx, into, to_end, whole = xs
        # what the state this chunk enters with adds at each position ...
        y_in = jnp.einsum("bihe,bhef->bihf", qx.astype(f32) * into[..., None],
                          state, precision=hi)
        # ... and what the chunk adds to the state by its end
        state = whole[..., None, None] * state + jnp.einsum(
            "bjhe,bjhf->bhef", kx.astype(f32) * to_end[..., None],
            vx.astype(f32), precision=hi)
        return state, y_in

    state, y_in = lax.scan(
        chunk, jnp.zeros((B, H, Dh, Dh), f32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (
            qc, kc, vc, jnp.exp(cum), jnp.exp(cum[:, :, -1:] - cum),
            jnp.exp(cum[:, :, -1]))))
    y = y + jnp.moveaxis(y_in, 0, 1)
    return y.reshape(B, S, H, Dh), state
