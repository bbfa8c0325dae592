"""Minimal decoder-only transformer LM — the long-context model family.

The reference has no attention and no sequence axis (SURVEY.md §5.7); this
model exists to exercise the framework's long-context path end-to-end:
ring / Ulysses sequence parallelism (parallel/sp.py) under a real training
loop, not just as an op-level demo.

Design for SPMD: `apply` is written to run either as a plain global
program or INSIDE shard_map with the sequence dim sharded —

- token embedding, layernorm, and the MLP are per-position (shard-local);
- positions are explicit (`pos_offset`), so a sequence shard can compute
  its true absolute positions from its axis index;
- attention is pluggable (`attn_fn`): the full-attention oracle by
  default, ring/Ulysses bodies under shard_map.

Numerics: master params are f32; `compute_dtype=jnp.bfloat16` runs every
matmul (and the residual stream) in bf16 — the MXU's native path — with
layernorms and the softmax/loss still computed in f32. Pre-LN blocks;
learned position embeddings.
"""

from __future__ import annotations

import dataclasses
import math
from collections.abc import Callable

import jax
import jax.numpy as jnp

from ..ops.attention import attention, rope, yarn_inv_freq
from ..ops.pallas_gemv import QuantW, qmatmul, swiglu


def _weight_cast(cd):
    """The compute-dtype weight cast, QuantW-aware: quantized decode
    weights (ops/pallas_gemv) carry their own storage dtype and must
    not be astype'd — qmatmul dequantizes them inside its kernel."""
    if cd is None:
        return lambda t: t
    return lambda t: t if isinstance(t, QuantW) else t.astype(cd)


def _layernorm(x, g, b, eps=1e-5):
    """Layernorm with the statistics in f32 regardless of x.dtype (bf16
    means/variances lose ~3 decimal digits); output back in x.dtype."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * g + b
    return y.astype(x.dtype)


def _rmsnorm(x, g, eps):
    """x * rsqrt(mean x^2 + eps) * g, statistics in f32 like
    _layernorm; no mean is taken off and there is no bias."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g).astype(x.dtype)


def norm(x, p: dict, eps: float):
    """A block's normalisation, by what its params hold: gain and bias
    is LayerNorm, a gain alone is RMSNorm."""
    if "b" in p:
        return _layernorm(x, p["g"], p["b"], eps)
    return _rmsnorm(x, p["g"], eps)


@dataclasses.dataclass(frozen=True)
class LatentAttn:
    """Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434):
    queries through a `q_rank` bottleneck, keys and values through one
    shared `kv_rank` latent a token plus ONE rotary key of `rope` dims
    for all heads. The cache holds that row (kv_rank + rope values) and
    no per-head K or V; each head has `nope` unrotated and `rope`
    rotated query dims and `v` value dims. `yarn`: (factor, original
    length, beta_fast, beta_slow, mscale, mscale_all_dim) or None."""

    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    rope_theta: float = 10000.0
    yarn: tuple | None = None

    @property
    def row(self) -> int:
        return self.kv_rank + self.rope

    def _mscale(self, m: float) -> float:
        factor = self.yarn[0]
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    @property
    def softmax_scale(self) -> float:
        """1/sqrt(nope + rope), times YaRN's mscale(mscale_all_dim)^2."""
        scale = 1.0 / math.sqrt(self.nope + self.rope)
        if self.yarn is None:
            return scale
        return scale * self._mscale(self.yarn[5]) ** 2

    def rotate(self, x, positions):
        """x (B, S, H, rope) rotated at `positions`, rotate-half pairs."""
        if self.yarn is None:
            return rope(x, positions, base=self.rope_theta)
        factor, original, fast, slow, mscale, mscale_all = self.yarn
        if self._mscale(mscale) != self._mscale(mscale_all):
            raise ValueError("YaRN with mscale != mscale_all_dim scales "
                             "cos/sin; not implemented")
        return rope(x, positions, inv_freq=yarn_inv_freq(
            self.rope, base=self.rope_theta, factor=factor,
            original_len=original, beta_fast=fast, beta_slow=slow))


@dataclasses.dataclass(frozen=True)
class RoutedExperts:
    """The expert layer of one chip (parallel/ep.moe_held_inference):
    the router scores all `experts` of the layer and takes `top_k` a
    token, weights normalised and times `scale`; `held` are the ids
    whose weights are here, and only they are computed.

    `router`: "sigmoid" is DeepSeek-V3's (a bias that only chooses,
    `groups` groups of which `top_groups` stay: ep.route_grouped),
    "softmax" a softmax over all experts and its top_k, no bias and no
    groups (ep.route_softmax). `act` is the gate branch's activation of
    a held expert: "silu" (SwiGLU) or "relu" (ReGLU). `reads` says
    which tensor the router scores: "block", the normed stream after
    attention that the experts themselves read, or "layer_input", the
    layer's residual input before its first norm — the choice is then
    made before attention and used after it."""

    experts: int
    held: tuple[int, ...]
    top_k: int
    groups: int = 1
    top_groups: int = 1
    scale: float = 1.0
    router: str = "sigmoid"
    act: str = "silu"
    reads: str = "block"

    def __post_init__(self):
        for name, value, known in (
                ("router", self.router, ("sigmoid", "softmax")),
                ("act", self.act, ("silu", "relu")),
                ("reads", self.reads, ("block", "layer_input"))):
            if value not in known:
                raise ValueError(f"RoutedExperts.{name} {value!r}: want "
                                 f"one of {known}")


@dataclasses.dataclass(frozen=True)
class SparseSelect:
    """Block selection of a softmax layer (InfLLM-V2, arXiv:2509.24663;
    the MiniCPM4 family's `sparse_config`): a query reads, a K/V head,
    the first `init_blocks` blocks of `block` keys, the blocks that
    overlap its last `window` keys, and the `topk` best-scored of the
    rest; while its position + 1 < `dense_len` it reads every block. A
    block's score is the largest, over the COMPRESSED keys that overlap
    it (the mean of `kernel` keys every `stride`), of the sum over the
    K/V head's query heads of their softmax over all complete
    compressed keys. There are no parameters: the selection reads q and
    the cached k alone (serve/paged_cache.select_blocks)."""

    kernel: int = 32
    stride: int = 16
    block: int = 64
    topk: int = 64
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        if (min(self.kernel, self.stride, self.block, self.topk) < 1
                or self.kernel % self.stride or self.block % self.stride
                or self.kernel > self.block):
            raise ValueError(f"{self}: want a kernel and a block of whole "
                             "strides, the kernel no longer than a block")

    def compressed(self, rows):
        """Compressed keys that are complete once `rows` keys are there
        (an array): key j covers rows j * stride .. j * stride + kernel
        - 1."""
        return jnp.maximum(rows - self.kernel + self.stride, 0) // self.stride


@dataclasses.dataclass(frozen=True)
class LinearAttn:
    """Lightning attention (linear attention with a per-head decay;
    Qin et al., arXiv:2401.04658): a layer keeps, a head, ONE
    (head_dim, head_dim) f32 state S and no keys: S_t = l_h S_{t-1} +
    k_t^T v_t, o_t = q_t S_t / sqrt(head_dim), l_h = exp(-2^(-`slope`
    (h + 1) / heads)) (ALiBi-style slopes, the same in every layer).
    The state is the slot's, not a page's
    (serve/paged_cache.SlotStates)."""

    slope: float = 8.0

    def log_decay(self, heads: int):
        """(heads,) f32: log l_h, negative."""
        return -(2.0 ** (-self.slope * jnp.arange(1, heads + 1,
                                                  dtype=jnp.float32) / heads))


@dataclasses.dataclass(frozen=True)
class TransformerLM:
    """Decoder-only LM: vocab -> dim, `depth` pre-LN blocks, tied LN head.

    Sizes are kept explicit; heads must divide dim. `init` and `apply`
    (the trainers) build and run one block: LayerNorm, K/V heads, the
    standard 4x GELU MLP. The cached decode forward
    (models/generate.token_forward) reads each layer's kind off its
    params instead (`norm`, `mlp`), and `attn` / `experts` /
    `head_width` / `layout` / `mixers` / `select` / `linear` and the
    three scales below describe what the params alone
    cannot: latent attention's sizes, an expert layer's routing, a head
    width that is not dim / heads, which layers rotate and which
    see a sliding window only, which layers keep a recurrent state
    instead of keys, which blocks a softmax layer's query reads, and
    the muP scalings of the embedding, the residual branches and the
    logits. Such a model brings its own params tree
    and is served through serve.PagedEngine.

    TPU sizing note (measured, PERF.md round-4 MFU ladder): prefer
    head_dim = dim/heads = 128 — the flash kernel's QK^T and PV dots
    contract over head_dim, and 128 fills the MXU's lanes exactly
    (head_dim 64 half-fills them: h=16 -> h=8 at d=1024 alone was
    +13.5 MFU points, 44.9% -> 58.4%).
    """

    vocab: int = 64
    dim: int = 64
    heads: int = 4
    depth: int = 2
    max_seq: int = 256
    kv_heads: int = 0      # 0 = heads (MHA); < heads = grouped-query
                           # attention (1 = MQA): k/v projections and the
                           # KV cache shrink by heads/kv_heads
    pos: str = "learned"   # learned | rope (rotary, ops/attention.rope —
                           # no position table, exact under SP shards via
                           # explicit absolute positions)
    moe_experts: int = 0   # 0 = dense MLP; >0 = Switch-MoE MLP per block
                           # (parallel/ep.py), EP-shardable over a mesh axis
    moe_top_k: int = 1     # experts per token: 1 = Switch, 2 = GShard-style
    norm_eps: float = 1e-5
    attn: LatentAttn | None = None         # None = K/V heads as above
    experts: RoutedExperts | None = None   # routing of a block that
                           # holds an "experts" bank (serving only)
    head_width: int = 0    # 0 = dim / heads; else each head's width,
                           # heads x head_width need not be dim
    rope_theta: float = 10000.0            # rotary base where pos="rope"
    window: int = 0        # keys a windowed layer's query sees, itself
                           # included (0 = the model has no such layer)
    layout: tuple[tuple[bool, bool], ...] | None = None
                           # per layer (rotary, windowed); None = every
                           # layer as `pos` says, none windowed
    mixers: tuple[str, ...] | None = None
                           # per layer "attn" (softmax over cached K/V)
                           # or "linear" (a recurrent state, `linear`);
                           # None = every layer "attn"
    select: SparseSelect | None = None     # the "attn" layers read the
                           # blocks this selects, not every key
    linear: LinearAttn | None = None       # the "linear" layers' decay
    emb_scale: float = 1.0       # the embedding times this (muP)
    residual_scale: float = 1.0  # every residual branch times this
    logit_scale: float = 1.0     # the final norm's output times this
    name: str = "transformer_lm"

    def __post_init__(self):
        if self.mixers is not None:
            if (len(self.mixers) != self.depth
                    or set(self.mixers) - {"attn", "linear"}):
                raise ValueError(f"mixers {self.mixers}: want {self.depth} "
                                 "of 'attn' / 'linear'")
            if ("linear" in self.mixers) != (self.linear is not None):
                raise ValueError(f"linear {self.linear} with the mixers "
                                 f"{self.mixers}")
        elif self.linear is not None:
            raise ValueError("a linear mixer needs `mixers` to say which "
                             "layers it is")
        if (self.select is not None or self.mixers is not None) and (
                self.attn is not None or self.window):
            raise ValueError("block selection and linear layers are of "
                             "K/V heads without a sliding window")
        if self.layout is None:
            if self.window:
                raise ValueError("a window needs a layout that says which "
                                 "layers it applies to")
            return
        if self.pos != "rope" or self.attn is not None:
            raise ValueError("a per-layer layout is of K/V heads with "
                             "pos='rope' (a layer without rotary has no "
                             "positional encoding at all)")
        if len(self.layout) != self.depth:
            raise ValueError(f"layout of {len(self.layout)} layers for "
                             f"depth {self.depth}")
        if any(w for _, w in self.layout) != (self.window > 0):
            raise ValueError(f"window {self.window} with the windowed "
                             f"layers {[w for _, w in self.layout]}")

    @property
    def head_dim(self) -> int:
        if self.head_width:
            return self.head_width
        if self.dim % self.heads:
            raise ValueError(f"dim {self.dim} not divisible by heads {self.heads}")
        return self.dim // self.heads

    def rotary(self, layer: int | None) -> bool:
        """Whether `layer`'s q and k are rotated."""
        if self.layout is None or layer is None:
            return self.pos == "rope"
        return bool(self.layout[layer][0])

    def layer_window(self, layer: int) -> int:
        """The sliding window of `layer`, 0 where it sees every key."""
        if self.layout is None or not self.layout[layer][1]:
            return 0
        return self.window

    def mixer(self, layer: int) -> str:
        """"attn" or "linear": what `layer` mixes positions with."""
        return "attn" if self.mixers is None else self.mixers[layer]

    def cache_groups(self) -> tuple[tuple[int, tuple[int, ...]], ...]:
        """The layer groups of the paged cache, (window, layers) each:
        layers that forget at the same distance share a page pool's
        accounting and a block table. The global layers (window 0)
        first; a model without windowed layers has the one group. The
        "linear" layers are in none of them: their group holds NO
        pages (`state_layers`)."""
        groups = {}
        for i in range(self.depth):
            if self.mixer(i) == "attn":
                groups.setdefault(self.layer_window(i), []).append(i)
        return tuple((w, tuple(groups[w])) for w in sorted(groups))

    def state_layers(self) -> tuple[int, ...]:
        """The page-less layer group: the "linear" layers, each of
        which keeps one fixed (heads, head_dim, head_dim) f32 array a
        slot whatever the slot's depth (paged_cache.SlotStates)."""
        return tuple(i for i in range(self.depth)
                     if self.mixer(i) == "linear")

    @property
    def n_kv(self) -> int:
        hkv = self.kv_heads or self.heads
        if hkv <= 0 or self.heads % hkv:
            # <= 0 must be caught explicitly: heads % -1 == 0 in Python,
            # and a negative count would flow into param shapes.
            raise ValueError(
                f"kv_heads must be a positive divisor of heads "
                f"{self.heads}; got {hkv}"
            )
        return hkv

    def _gpt2_block_only(self, what: str) -> None:
        if (self.attn is not None or self.experts is not None
                or self.head_width or self.layout is not None
                or self.mixers is not None or self.select is not None
                or (self.emb_scale, self.residual_scale,
                    self.logit_scale) != (1.0, 1.0, 1.0)):
            raise ValueError(
                f"TransformerLM.{what} knows the LayerNorm/GELU block with "
                "K/V heads; a model with latent attention, held experts, "
                "its own head width, a per-layer layout, block selection, "
                "linear layers or muP scalings brings its "
                "params tree and is served through "
                "serve.PagedEngine (models/generate.token_forward)")

    def init(self, key) -> dict:
        self._gpt2_block_only("init")
        d, v, hd = self.dim, self.vocab, self.head_dim
        # Key budget is fixed regardless of config so the default
        # (learned-pos MHA) consumes keys exactly as in round 1 — golden
        # params stay reproducible; GQA draws one extra subkey from the
        # block key instead of shifting the stream.
        keys = iter(jax.random.split(key, 3 + 4 * self.depth))
        scale = 1.0 / math.sqrt(d)

        def dense(k, din, dout):
            return jax.random.normal(k, (din, dout), jnp.float32) / math.sqrt(din)

        params = {
            "tok_emb": jax.random.normal(next(keys), (v, d), jnp.float32) * scale,
            "ln_f": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "blocks": [],
        }
        pos_key = next(keys)  # drawn even for rope: keeps the stream fixed
        if self.pos == "learned":
            params["pos_emb"] = jax.random.normal(
                pos_key, (self.max_seq, d), jnp.float32
            ) * scale
        elif self.pos != "rope":
            raise ValueError(f"unknown pos {self.pos!r}; 'learned' or 'rope'")
        params["head"] = dense(next(keys), d, v)
        for _ in range(self.depth):
            blk = {
                "ln1": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
                "ln2": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            }
            qkv_key = next(keys)
            if self.n_kv == self.heads:
                blk["wqkv"] = dense(qkv_key, d, 3 * d)
            else:
                kq, kkv = jax.random.split(qkv_key)
                blk["wq"] = dense(kq, d, d)
                blk["wkv"] = dense(kkv, d, 2 * self.n_kv * hd)
            blk["wo"] = dense(next(keys), d, d)
            if self.moe_experts:
                from ..parallel.ep import init_moe_params

                blk["moe"] = init_moe_params(
                    next(keys), d, 4 * d, self.moe_experts
                )
                next(keys)  # keep the per-block key budget uniform
            else:
                blk["w1"] = dense(next(keys), d, 4 * d)
                blk["w2"] = dense(next(keys), 4 * d, d)
            params["blocks"].append(blk)
        return params

    def project_qkv(
        self,
        blk: dict,
        y: jnp.ndarray,                # (B, S, dim) normed activations
        *,
        positions: jnp.ndarray,        # (S,) or (B, S) absolute positions
        compute_dtype=None,
        layer: int | None = None,      # which layer (a `layout` is per layer)
    ):
        """QKV projections + head reshape + rotary — THE one
        implementation, shared by the training forward (apply_block) and
        the cached decode core (models/generate.token_forward, which the
        contiguous decode_block AND serve/'s paged path both ride).
        Before the serve/ refactor the decode path re-implemented these
        lines and only a parity test bound the two; now they cannot
        drift. Per-row (B, S) positions are the continuous-batching
        decode form — each serving slot sits at its own depth. Weight
        matmuls route through qmatmul, so serving params may carry int8
        QuantW leaves (quantize_decode_params) — the decode-weight
        bandwidth lever, same forward.
        Returns q: (B, S, H, hd); k, v: (B, S, Hkv, hd)."""
        b, s, _ = y.shape
        w = _weight_cast(compute_dtype)
        if self.attn is not None:
            return self._project_latent(blk, y, positions, w)
        h, hd, hkv = self.heads, self.head_dim, self.n_kv
        if layer is not None and self.mixer(layer) == "linear":
            hkv = h         # a linear layer's k and v are a head each
        if hkv == h:
            qkv = qmatmul(y, w(blk["wqkv"]))        # (B, S, 3*dim)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            q = qmatmul(y, w(blk["wq"]))            # (B, S, dim)
            kv = qmatmul(y, w(blk["wkv"]))          # (B, S, 2*hkv*hd)
            k, v = jnp.split(kv, 2, axis=-1)
        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s, hkv, hd)
        v = v.reshape(b, s, hkv, hd)
        if "q_norm" in blk:     # RMSNorm over each head's dims, a gain
            q = _rmsnorm(q, blk["q_norm"]["g"], self.norm_eps)
            k = _rmsnorm(k, blk["k_norm"]["g"], self.norm_eps)
        if self.rotary(layer):
            q = rope(q, positions, base=self.rope_theta)
            k = rope(k, positions, base=self.rope_theta)
        return q, k, v

    def _project_latent(self, blk, y, positions, w):
        """project_qkv under latent attention. Returns q: (B, S, H,
        nope + rope) with the rope part rotated; the token's cache row
        [RMS(c) ; rope(k_r)] as k: (B, S, 1, kv_rank + rope) — one row
        for all heads; v: None (values are read out of the same row)."""
        a, b, s = self.attn, y.shape[0], y.shape[1]
        cq = _rmsnorm(qmatmul(y, w(blk["wdq"])), blk["q_norm"]["g"],
                      self.norm_eps)
        # The query up-projection is held head first, the heads' nope
        # part and their rope part apart, (H, q_rank, nope | rope): one
        # (q_rank, H x 192) matrix has a head stride no lane tile
        # divides, and the compiler then re-lays the whole matrix in
        # every program (PERF.md section 6, PR 28).
        qn = jnp.einsum("bsr,hrn->bshn", cq, w(blk["wuq_n"]))
        qr = jnp.einsum("bsr,hrn->bshn", cq, w(blk["wuq_r"]))
        q = jnp.concatenate([qn, a.rotate(qr, positions)], axis=-1)
        ckr = qmatmul(y, w(blk["wdkv"]))                   # (B, S, row)
        c = _rmsnorm(ckr[..., :a.kv_rank], blk["kv_norm"]["g"],
                     self.norm_eps)
        kr = a.rotate(ckr[..., None, a.kv_rank:], positions)
        return q, jnp.concatenate([c[..., None, :], kr], axis=-1), None

    def route(self, blk: dict, x: jnp.ndarray):
        """The routing of an expert layer decided from its INPUT x
        (B, S, dim), where the configuration says the router reads
        there (`experts.reads` "layer_input"): (ids, weights) of
        (B*S, top_k) for `mlp`; None for every other layer, whose
        router (if any) reads what its experts read."""
        if ("experts" not in blk or self.experts is None
                or self.experts.reads != "layer_input"):
            return None
        from ..parallel.ep import route

        return route(x.reshape(-1, x.shape[-1]), blk["router"], self.experts)

    def mlp(self, blk: dict, y: jnp.ndarray, valid=None, routing=None):
        """The block's feed-forward on y (B, S, dim), by what the block
        holds: `w1`/`w2` the GELU MLP, `wg`/`wu`/`wd` a gated (SwiGLU)
        one at whatever width the matrices have, `experts` this chip's
        share of a routed expert layer (only rows in `valid` are
        routed; `routing` is `route`'s where the choice was made from
        the layer's input, else the layer routes on y). Returns (out,
        counts): counts the expert layer's int32 [token-expert pairs
        computed, held experts hit, largest load], None elsewhere."""
        if "experts" in blk:
            from ..parallel.ep import moe_held_inference

            b, s, d = y.shape
            m, counts = moe_held_inference(
                y.reshape(b * s, d), blk, self.experts,
                valid=None if valid is None else valid.reshape(b * s),
                routing=routing)
            return m.reshape(b, s, d), counts
        if self.moe_experts:
            from ..parallel.ep import moe_mlp_inference

            b, s, d = y.shape
            m = moe_mlp_inference(
                y.reshape(b * s, d), blk["moe"],
                n_experts=self.moe_experts, top_k=self.moe_top_k,
            )
            return m.reshape(b, s, d), None
        if "wg" in blk:
            return swiglu(y, blk), None
        return qmatmul(jax.nn.gelu(qmatmul(y, blk["w1"])), blk["w2"]), None

    def apply_block(
        self,
        blk: dict,
        x: jnp.ndarray,                # (B, S, dim) activations
        *,
        pos: jnp.ndarray,              # (S,) absolute positions
        attn,                          # (q, k, v) -> o attention callable
        compute_dtype=None,
        moe_axis: str | None = None,
        moe_inference: bool = False,
        moe_dispatch_chunk: int = 0,
        moe_dispatch_dtype=None,
    ):
        """One pre-LN block: attention + MLP (or MoE) with residuals.

        Factored out of apply() so pipeline parallelism (parallel/pp_lm.py)
        can scan the SAME block computation over its stage's stacked
        params — one implementation of the block math for every layout.
        Returns (x, aux) with aux the MoE balance loss (0 for dense).
        """
        self._gpt2_block_only("apply")
        b, s, _ = x.shape
        h, hd = self.heads, self.head_dim
        cd = compute_dtype
        w = _weight_cast(cd)

        y = _layernorm(x, blk["ln1"]["g"], blk["ln1"]["b"])
        q, k, v = self.project_qkv(blk, y, positions=pos, compute_dtype=cd)
        o = attn(q, k, v).reshape(b, s, h * hd)
        x = x + qmatmul(o.astype(x.dtype), w(blk["wo"]))
        y = _layernorm(x, blk["ln2"]["g"], blk["ln2"]["b"])
        if self.moe_experts:
            # Expert weights go through the same compute-dtype cast
            # as the dense matmuls (the router's softmax stays f32
            # inside moe_mlp); without this the 16d² expert FLOPs
            # would silently promote back to f32.
            moe_p = jax.tree.map(w, blk["moe"]) if cd else blk["moe"]
            if moe_inference:
                from ..parallel.ep import moe_mlp_inference

                m = moe_mlp_inference(
                    y.reshape(b * s, self.dim), moe_p,
                    n_experts=self.moe_experts, top_k=self.moe_top_k,
                )
                aux = jnp.zeros(())
            else:
                from ..parallel.ep import moe_mlp

                m, aux = moe_mlp(
                    y.reshape(b * s, self.dim), moe_p,
                    n_experts=self.moe_experts, axis=moe_axis,
                    top_k=self.moe_top_k,
                    dispatch_chunk=moe_dispatch_chunk,
                    dispatch_dtype=moe_dispatch_dtype,
                )
            return x + m.reshape(b, s, self.dim).astype(x.dtype), aux
        return (
            x + qmatmul(jax.nn.gelu(qmatmul(y, w(blk["w1"]))),
                        w(blk["w2"])),
            jnp.zeros(()),
        )

    def apply(
        self,
        params: dict,
        tokens: jnp.ndarray,           # (B, S) int32
        *,
        attn_fn: Callable | None = None,
        pos_offset: jnp.ndarray | int = 0,
        causal: bool = True,
        remat: bool = False,           # jax.checkpoint per block
        moe_axis: str | None = None,   # mesh axis for EP expert sharding
                                       # (None = dense single-device MoE)
        moe_inference: bool = False,   # no-drop compute-all-experts MoE
                                       # (ep.moe_mlp_inference) — the
                                       # decode/prefill semantic
        moe_dispatch_chunk: int = 0,   # single-chip chunked routing
                                       # (ep.moe_mlp dispatch_chunk):
                                       # kills the quadratic dispatch
                                       # einsum term
        moe_dispatch_dtype=None,       # routing-tensor dtype override
                                       # (ep.moe_mlp dispatch_dtype);
                                       # bf16 halves the (T,E,C) build
                                       # bytes under an f32 path
        return_aux: bool = False,      # also return the MoE balance loss
        compute_dtype=None,            # e.g. jnp.bfloat16: run matmuls +
                                       # residual stream in this dtype
                                       # (master params stay f32; LN and
                                       # the caller's loss stay f32)
        return_features: bool = False,  # skip the head matmul and return
                                       # the final-LN features (B, S, dim)
                                       # — for losses that fuse the head
                                       # (train/lm.py chunked CE, which
                                       # never materializes (B,S,V) f32)
    ):                                 # (B, S, vocab) logits [, aux]
        b, s = tokens.shape
        h, hd = self.heads, self.head_dim
        cd = compute_dtype
        w = _weight_cast(cd)
        if s > self.max_seq:
            # XLA's gather would silently clamp out-of-range positions to
            # pos_emb[max_seq-1]; fail loudly instead. (Sharded callers
            # check the GLOBAL length — see make_sp_lm_train_step.)
            raise ValueError(f"sequence length {s} exceeds max_seq {self.max_seq}")
        attn = attn_fn or (lambda q, k, v: attention(q, k, v, causal=causal))
        hkv = self.n_kv

        pos = pos_offset + jnp.arange(s)
        x = params["tok_emb"][tokens]
        if self.pos == "learned":
            x = x + params["pos_emb"][pos][None, :, :]
        x = w(x)

        def block(blk, x):
            return self.apply_block(
                blk, x, pos=pos, attn=attn, compute_dtype=cd,
                moe_axis=moe_axis, moe_inference=moe_inference,
                moe_dispatch_chunk=moe_dispatch_chunk,
                moe_dispatch_dtype=moe_dispatch_dtype,
            )

        if remat:
            # Recompute block activations in the backward pass (the
            # long-context memory lever; composes with ring attention's
            # O(S/P) residency since attn_fn runs inside the checkpoint).
            block = jax.checkpoint(block)
        aux_total = jnp.zeros(())
        for blk in params["blocks"]:
            x, aux = block(blk, x)
            aux_total = aux_total + aux
        x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
        if return_features:
            return (x, aux_total) if return_aux else x
        # Head matmul in compute dtype (it is the single largest matmul);
        # logits come back in f32 — the loss softmax must not run in bf16.
        logits = qmatmul(x, w(params["head"])).astype(jnp.float32)
        return (logits, aux_total) if return_aux else logits
