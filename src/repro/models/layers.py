"""Shared neural layers: norms, rope, attention (GQA / MLA / cross), FFNs.

Pure functions over explicit param dicts. Weights are bf16 (cfg.dtype);
normalization and softmax accumulate in f32. All matmuls request f32
accumulation via preferred_element_type.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig

Params = dict[str, Any]


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dot(x, w):
    return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)


# --- init helpers ------------------------------------------------------------
def dense_init(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


def stacked(key, n, init_fn):
    """Stack n independent inits along axis 0 (scan-friendly params)."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


# --- RMSNorm ------------------------------------------------------------------
def rmsnorm(x, gamma, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * gamma.astype(x.dtype)


# --- RoPE ---------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta):
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs    # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]                   # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --- Attention (GQA, optional window / cross / bidirectional) -----------------
def attn_init(key, cfg: ModelConfig, d_kv_in: int | None = None) -> Params:
    """d_kv_in: source dim for k/v (cross-attention); defaults to d_model."""
    D, hd = cfg.d_model, cfg.hd
    d_kv_in = d_kv_in or D
    ks = jax.random.split(key, 4)
    dt = _dt(cfg)
    return {
        "wq": dense_init(ks[0], D, cfg.n_heads * hd, dt),
        "wk": dense_init(ks[1], d_kv_in, cfg.n_kv_heads * hd, dt),
        "wv": dense_init(ks[2], d_kv_in, cfg.n_kv_heads * hd, dt),
        "wo": dense_init(ks[3], cfg.n_heads * hd, D, dt),
    }


def _ambient_mesh():
    """The ambient mesh (``jax.set_mesh``), or None when none is set."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


# materializing (S, T) logits beyond this many query rows switches to the
# exact q-chunked path (bounds live memory to (B, H, CHUNK, T)).
_Q_CHUNK = 4096


def _flash_shardable(cfg: ModelConfig) -> bool:
    """Flash path needs an ambient mesh whose model axis divides the query
    heads (each rank runs the kernel on its local heads)."""
    mesh = _ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return False
    m = mesh.shape["model"]
    if cfg.n_heads % m:
        return False
    h_loc = cfg.n_heads // m
    if cfg.n_kv_heads % m == 0:
        return True
    # replicated-KV mode: each rank's q heads must map to a contiguous,
    # rank-constant set of kv heads
    g = cfg.n_heads // cfg.n_kv_heads
    return g % h_loc == 0 or h_loc % g == 0


def _flash_sdpa(cfg: ModelConfig, q, k, v, *, causal: bool,
                window: int | None):
    """(B, S, H, D) flash attention through the Pallas kernel, sharded with
    shard_map over (batch -> data axes, heads -> model). KV heads shard when
    divisible, otherwise replicate + local slice (GQA).

    On TPU the kernel compiles to Mosaic; on CPU it runs in interpret mode —
    either way the HLO carries the kernel's BlockSpec streaming as its HBM
    traffic (launch/hlo_analysis.py VMEM-scope rule)."""
    from repro.kernels import ops as kops   # local import: no cycle at load

    mesh = _ambient_mesh()
    m = mesh.shape["model"]
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b, s, h, d = q.shape
    hkv = k.shape[2]
    ba = ba if ba and b % max(
        1, int(np.prod([mesh.shape[a] for a in ba]))) == 0 else None
    h_loc = h // m
    kv_sharded = hkv % m == 0

    def local(qt, kt, vt):
        if not kv_sharded and hkv != h:
            # slice the kv heads this rank's q heads attend to
            r = jax.lax.axis_index("model")
            g = h // hkv
            n_kv_loc = max(h_loc // g, 1)
            start = (r * h_loc) // g
            kt = jax.lax.dynamic_slice_in_dim(kt, start, n_kv_loc, axis=1)
            vt = jax.lax.dynamic_slice_in_dim(vt, start, n_kv_loc, axis=1)
        return kops.flash_attention(qt, kt, vt, causal=causal,
                                    window=window)

    kv_spec = P(ba, "model" if kv_sharded else None, None, None)
    out = jax.shard_map(local, mesh=mesh,
                        in_specs=(P(ba, "model", None, None),
                                  kv_spec, kv_spec),
                        out_specs=P(ba, "model", None, None),
                        check_vma=False)(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)


def _sdpa(q, k, v, *, causal, window, q_pos=None, kv_len=None):
    """q: (B, S, H, D); k/v: (B, T, Hkv, D) -> (B, S, H, D).

    q_pos: (S,) absolute positions of queries (decode: T-1); kv_len: number of
    valid kv entries (decode with preallocated cache).
    """
    b, s, h, d = q.shape
    if s > _Q_CHUNK and s % _Q_CHUNK == 0:
        if q_pos is None:
            q_pos = jnp.arange(s)
        qs = q.reshape(b, s // _Q_CHUNK, _Q_CHUNK, h, d).transpose(1, 0, 2, 3, 4)
        ps = q_pos.reshape(s // _Q_CHUNK, _Q_CHUNK)
        out = jax.lax.map(
            lambda args: _sdpa(args[0], k, v, causal=causal, window=window,
                               q_pos=args[1], kv_len=kv_len), (qs, ps))
        return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, v.shape[-1])
    t, hkv = k.shape[1], k.shape[2]
    # GQA strategy (§Perf iteration 1): the grouped einsum never
    # materializes repeated K/V — on a TP mesh where hkv < |model| the KV
    # cache is sequence-sharded and jnp.repeat would force the partitioner
    # to all-gather the whole cache every layer (6.4e10 B/dev per decode
    # step on llama3-8b decode_32k). The grouped form keeps the
    # T-contraction sequence-sharded; only partial (B,S,H,D) sums cross
    # chips (flash-decoding parallelism, derived by the SPMD partitioner).
    # For TRAIN/PREFILL with hkv not divisible by the model axis, grouped
    # logits (B,hkv,g,S,T) lose their clean head sharding and cost MORE
    # (llama-3.2-vision-90b train: memory +11%) — use repeat there.
    mesh = _ambient_mesh()
    m = mesh.shape.get("model", 1) if mesh is not None \
        and hasattr(mesh, "shape") else 1
    grouped = (s == 1) or hkv % max(m, 1) == 0 or hkv == h
    if not grouped:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
        hkv = h
    g = h // hkv
    qg = q.reshape(b, s, hkv, g, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    qi = (q_pos if q_pos is not None else jnp.arange(s))[:, None]
    ki = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    if kv_len is not None:
        mask &= ki < kv_len
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    # v's head dim may differ from q/k's (MLA: dv=128 vs dqk=192)
    return out.reshape(b, s, h, v.shape[-1]).astype(q.dtype)


def attention(p: Params, cfg: ModelConfig, x, *, kv_src=None, positions=None,
              causal=True, cache=None, use_rope=True):
    """Self- or cross-attention. x: (B, S, D).

    cache: None (train/prefill, no cache) or dict {k, v, len} with
    preallocated (B, T, Hkv, hd) buffers for decode — returns (out, cache').
    kv_src: (B, T, Dsrc) for cross-attention (no cache, no rope on kv).
    """
    B, S, D = x.shape
    hd = cfg.hd
    src = kv_src if kv_src is not None else x
    q = dot(x, p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = dot(src, p["wk"]).reshape(B, src.shape[1], cfg.n_kv_heads, hd)
    v = dot(src, p["wv"]).reshape(B, src.shape[1], cfg.n_kv_heads, hd)
    if positions is None:
        positions = jnp.arange(S)
    if use_rope and kv_src is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    use_flash = (cfg.attn_impl == "flash" and kv_src is None and S > 1
                 and _flash_shardable(cfg))
    if cache is not None:
        # decode (S==1) or prefill (S>1): write k/v at position cache["len"]
        idx = cache["len"]
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, idx, axis=1)
        if use_flash:
            # prefill starts from an empty cache (idx == 0), so attention
            # over the in-flight k/v equals attention over the cache
            out = _flash_sdpa(cfg, q, k, v, causal=True,
                              window=cfg.attn_window)
        else:
            out = _sdpa(q, ck, cv, causal=True, window=cfg.attn_window,
                        q_pos=positions, kv_len=idx + S)
        new_cache = {"k": ck, "v": cv, "len": idx + S}
        return dot(out.reshape(B, S, cfg.n_heads * hd), p["wo"]), new_cache
    if use_flash:
        out = _flash_sdpa(cfg, q, k, v, causal=causal,
                          window=cfg.attn_window)
    else:
        out = _sdpa(q, k, v, causal=causal and kv_src is None,
                    window=cfg.attn_window)
    return dot(out.reshape(B, S, cfg.n_heads * hd), p["wo"]), None


# --- MLA (deepseek multi-head latent attention) --------------------------------
def mla_init(key, cfg: ModelConfig) -> Params:
    D = cfg.d_model
    qk_hd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    dt = _dt(cfg)
    return {
        "wdq": dense_init(ks[0], D, cfg.q_lora_rank, dt),
        "wuq": dense_init(ks[1], cfg.q_lora_rank, cfg.n_heads * qk_hd, dt),
        "wdkv": dense_init(ks[2], D, cfg.kv_lora_rank, dt),
        "wkr": dense_init(ks[3], D, cfg.qk_rope_head_dim, dt),
        "wuk": dense_init(ks[4], cfg.kv_lora_rank,
                          cfg.n_heads * cfg.qk_nope_head_dim, dt),
        "wuv": dense_init(ks[5], cfg.kv_lora_rank,
                          cfg.n_heads * cfg.v_head_dim, dt),
        "wo": dense_init(ks[6], cfg.n_heads * cfg.v_head_dim, D, dt),
    }


def mla_attention(p: Params, cfg: ModelConfig, x, *, positions=None,
                  cache=None):
    """Multi-head latent attention. Cache (decode) holds only the compressed
    kv latent (B, T, kv_lora_rank) + rope key (B, T, rope_hd) — the paper's
    (DeepSeek-V3) KV-cache reduction. Decode uses the absorbed-matmul form.
    """
    B, S, D = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(S)
    q = dot(dot(x, p["wdq"]), p["wuq"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    kv_c = dot(x, p["wdkv"])                                # (B, S, R)
    k_rope = apply_rope(dot(x, p["wkr"])[:, :, None, :], positions,
                        cfg.rope_theta)                     # (B, S, 1, dr)
    scale = (dn + dr) ** -0.5

    if cache is None:
        k_nope = dot(kv_c, p["wuk"]).reshape(B, S, H, dn)
        v = dot(kv_c, p["wuv"]).reshape(B, S, H, dv)
        # concat nope+rope into one head dim: q'.k' = nope.nope + rope.rope,
        # so the (q-chunked) shared SDPA computes MLA logits exactly; its
        # scale (dn+dr)^-0.5 matches `scale`.
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope.astype(k_nope.dtype),
                                      (B, S, H, dr))], axis=-1)
        out = _sdpa(q_full, k_full, v, causal=True, window=None)
        return dot(out.reshape(B, S, H * dv), p["wo"]), None

    # decode (S == 1), absorbed form: score in latent space.
    idx = cache["len"]
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["kv_c"], kv_c, idx, axis=1)
    ckr = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope[:, :, 0, :], idx, axis=1)
    wuk = p["wuk"].reshape(cfg.kv_lora_rank, H, dn)
    q_c = jnp.einsum("bshd,rhd->bshr", q_nope, wuk,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    logits = (jnp.einsum("bshr,btr->bhst", q_c, ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshd,btd->bhst", q_rope, ckr,
                           preferred_element_type=jnp.float32)) * scale
    t = ckv.shape[1]
    ki = jnp.arange(t)[None, None, None, :]
    qi = positions[None, None, :, None]
    logits = jnp.where((ki < idx + S) & (ki <= qi), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    o_c = jnp.einsum("bhst,btr->bshr", probs, ckv,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    wuv = p["wuv"].reshape(cfg.kv_lora_rank, H, dv)
    out = jnp.einsum("bshr,rhd->bshd", o_c, wuv,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    new_cache = {"kv_c": ckv, "k_rope": ckr, "len": idx + S}
    return dot(out.reshape(B, S, H * dv), p["wo"]), new_cache


# --- FFN (swiglu / geglu) -------------------------------------------------------
def ffn_init(key, cfg: ModelConfig, d_ff: int | None = None) -> Params:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = _dt(cfg)
    return {"w1": dense_init(ks[0], D, F, dt),
            "w3": dense_init(ks[1], D, F, dt),
            "w2": dense_init(ks[2], F, D, dt)}


def ffn(p: Params, cfg: ModelConfig, x):
    gate = dot(x, p["w1"])
    act = jax.nn.gelu(gate.astype(jnp.float32)).astype(x.dtype) \
        if cfg.ffn_kind == "geglu" else jax.nn.silu(gate)
    return dot(act * dot(x, p["w3"]), p["w2"])
