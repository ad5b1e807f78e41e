"""Unified LM transformer: dense GQA / MLA attention, dense / MoE FFN.

One config covers qwen2.5-14b, llama3-405b, internlm2-20b (dense GQA),
deepseek-v2-lite (MLA + MoE), kimi-k2 (GQA + MoE). Layers are scanned
(stacked params, one compiled layer body) with full per-layer remat —
mandatory for the 405B/1T dry-runs to fit and to keep CPU compile sane.

Three lowered entry points per arch (assignment §shapes):
  train_step    fwd + bwd + optimizer        (train_4k)
  prefill_step  fwd, returns last-logits+KV  (prefill_32k)
  decode_step   1 token against a KV cache   (decode_32k / long_500k),
                KV sequence-sharded, split-K flash combine (SP) — the
                sharding axes come from the per-shape rule table, so
                decode_32k shards seq over "model" and long_500k (batch=1)
                over ("data","model").

MLA caches the 576-wide latent (kv_lora + rotated k_rope), expanded
shard-locally at decode — the memory story that motivates MLA.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..optim import adafactor, adamw
from ..parallel.collectives import flash_combine
from ..parallel.sharding import RULES, logical_to_spec
from . import moe as moe_lib
from .layers import cross_entropy, flash_attention, init_dense, rms_norm, rope, swiglu_apply

__all__ = ["LMConfig", "MLAConfig", "TransformerLM"]


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def cache_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    attn: str = "gqa"  # "gqa" | "mla"
    mla: Optional[MLAConfig] = None
    moe: Optional[moe_lib.MoEConfig] = None
    rope_theta: float = 1e6
    dtype: Any = jnp.bfloat16
    optimizer: str = "adamw"  # "adamw" | "adafactor"
    attn_chunk: int = 1024

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    # ---- parameter accounting (MODEL_FLOPS = 6 N D / 6 N_active D) --------
    def _attn_params(self) -> int:
        d = self.d_model
        if self.attn == "mla":
            m = self.mla or MLAConfig()
            return (
                d * self.n_heads * m.qk_dim
                + d * m.cache_dim
                + m.kv_lora_rank * self.n_heads * (m.qk_nope_dim + m.v_head_dim)
                + self.n_heads * m.v_head_dim * d
            )
        base = d * self.dh * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * self.dh * d
        if self.qkv_bias:
            base += self.dh * (self.n_heads + 2 * self.n_kv_heads)
        return base

    def n_params(self) -> int:
        d = self.d_model
        dense_layer = self._attn_params() + 3 * d * self.d_ff + 2 * d
        total = 2 * self.vocab * d + d
        if self.moe is None:
            return total + self.n_layers * dense_layer
        e = self.moe
        moe_layer = (
            self._attn_params()
            + d * e.n_experts
            + 3 * e.n_experts * d * e.d_ff_expert
            + 3 * d * e.d_ff_expert * e.n_shared
            + 2 * d
        )
        return total + e.first_dense * dense_layer + (self.n_layers - e.first_dense) * moe_layer

    def n_active_params(self) -> int:
        if self.moe is None:
            return self.n_params()
        d = self.d_model
        e = self.moe
        dense_layer = self._attn_params() + 3 * d * self.d_ff + 2 * d
        act_layer = (
            self._attn_params()
            + d * e.n_experts
            + 3 * d * e.d_ff_expert * (e.top_k + e.n_shared)
            + 2 * d
        )
        return (
            2 * self.vocab * d
            + d
            + e.first_dense * dense_layer
            + (self.n_layers - e.first_dense) * act_layer
        )


# ============================================================ parameter trees
def _init_attn(key, cfg: LMConfig):
    d, h, g, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh
    ks = jax.random.split(key, 4)
    if cfg.attn == "mla":
        m = cfg.mla or MLAConfig()
        return {
            "w_q": init_dense(ks[0], (d, h * m.qk_dim), cfg.dtype),
            "w_dkv": init_dense(ks[1], (d, m.cache_dim), cfg.dtype),
            "w_ukv": init_dense(
                ks[2], (m.kv_lora_rank, h * (m.qk_nope_dim + m.v_head_dim)), cfg.dtype
            ),
            "w_o": init_dense(ks[3], (h * m.v_head_dim, d), cfg.dtype),
        }
    p = {
        "w_q": init_dense(ks[0], (d, h * dh), cfg.dtype),
        "w_k": init_dense(ks[1], (d, g * dh), cfg.dtype),
        "w_v": init_dense(ks[2], (d, g * dh), cfg.dtype),
        "w_o": init_dense(ks[3], (h * dh, d), cfg.dtype),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((h * dh,), cfg.dtype)
        p["b_k"] = jnp.zeros((g * dh,), cfg.dtype)
        p["b_v"] = jnp.zeros((g * dh,), cfg.dtype)
    return p


def _logical_attn(cfg: LMConfig):
    if cfg.attn == "mla":
        return {
            "w_q": ("embed", "heads"),
            "w_dkv": ("embed", None),
            "w_ukv": (None, "heads"),
            "w_o": ("heads", "embed"),
        }
    lg = {
        "w_q": ("embed", "heads"),
        "w_k": ("embed", "kv_heads"),
        "w_v": ("embed", "kv_heads"),
        "w_o": ("heads", "embed"),
    }
    if cfg.qkv_bias:
        lg.update({"b_q": ("heads",), "b_k": ("kv_heads",), "b_v": ("kv_heads",)})
    return lg


def _init_ffn(key, cfg: LMConfig):
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    return {
        "w_gate": init_dense(ks[0], (d, cfg.d_ff), cfg.dtype),
        "w_up": init_dense(ks[1], (d, cfg.d_ff), cfg.dtype),
        "w_down": init_dense(ks[2], (cfg.d_ff, d), cfg.dtype),
    }


_LOGICAL_FFN = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}


def _init_layer(key, cfg: LMConfig, is_moe: bool):
    k1, k2 = jax.random.split(key)
    p = {
        "norm1": jnp.ones((cfg.d_model,), cfg.dtype),
        "norm2": jnp.ones((cfg.d_model,), cfg.dtype),
        "attn": _init_attn(k1, cfg),
    }
    if is_moe:
        p["moe"] = moe_lib.init_moe(k2, cfg.moe, cfg.d_model, cfg.dtype)
    else:
        p["ffn"] = _init_ffn(k2, cfg)
    return p


def _logical_layer(cfg: LMConfig, is_moe: bool):
    lg = {"norm1": (None,), "norm2": (None,), "attn": _logical_attn(cfg)}
    if is_moe:
        lg["moe"] = moe_lib.logical_moe(cfg.moe)
    else:
        lg["ffn"] = dict(_LOGICAL_FFN)
    return lg


def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)


class TransformerLM:
    """Functional model: params are plain dicts, every step fn is pjit-able."""

    def __init__(self, cfg: LMConfig, mesh: Mesh, rules: Optional[Dict] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.rules = dict(RULES, **(rules or {}))
        self.dp_axes = tuple(
            a for a in self.rules.get("batch", ()) if a in mesh.axis_names
        )
        self.seq_axes = tuple(
            a for a in self.rules.get("seq_kv", ("model",)) if a in mesh.axis_names
        ) or ("model",)
        self.ff_axes = tuple(
            a for a in self.rules.get("expert_ff", ()) if a in mesh.axis_names
        )
        self.n_dense = cfg.moe.first_dense if cfg.moe else cfg.n_layers
        self.n_moe = cfg.n_layers - self.n_dense

    # -------------------------------------------------------------- params
    def init(self, key) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        params: Dict[str, Any] = {
            "embed": init_dense(ks[0], (cfg.vocab, cfg.d_model), cfg.dtype, scale=0.02),
            "out_proj": init_dense(ks[1], (cfg.d_model, cfg.vocab), cfg.dtype),
            "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        }
        if self.n_dense:
            keys = jax.random.split(ks[2], self.n_dense)
            params["dense_stack"] = jax.vmap(lambda k: _init_layer(k, cfg, False))(keys)
        if self.n_moe:
            keys = jax.random.split(ks[3], self.n_moe)
            params["moe_stack"] = jax.vmap(lambda k: _init_layer(k, cfg, True))(keys)
        return params

    def abstract_params(self) -> Dict:
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    def logical_tree(self) -> Dict:
        cfg = self.cfg
        stack = lambda lg: jax.tree.map(
            lambda t: (None,) + t, lg, is_leaf=_is_axes
        )
        tree: Dict[str, Any] = {
            "embed": ("vocab", "embed"),
            "out_proj": ("embed", "vocab"),
            "final_norm": (None,),
        }
        if self.n_dense:
            tree["dense_stack"] = stack(_logical_layer(cfg, False))
        if self.n_moe:
            tree["moe_stack"] = stack(_logical_layer(cfg, True))
        return tree

    def param_specs(self) -> Dict:
        return jax.tree.map(
            lambda lg: logical_to_spec(lg, self.mesh, self.rules),
            self.logical_tree(),
            is_leaf=_is_axes,
        )

    def _constrain(self, x, *logical):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, logical_to_spec(logical, self.mesh, self.rules))
        )

    # -------------------------------------------------------------- forward
    def _gqa_proj(self, p, x):
        cfg = self.cfg
        b, s, _ = x.shape
        h, g, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
        q = x @ p["w_q"]
        k = x @ p["w_k"]
        v = x @ p["w_v"]
        if cfg.qkv_bias:
            q, k, v = q + p["b_q"], k + p["b_k"], v + p["b_v"]
        return q.reshape(b, s, h, dh), k.reshape(b, s, g, dh), v.reshape(b, s, g, dh)

    def _mla_proj(self, p, x, positions):
        """Returns q (B,S,H,qk), k (B,S,H,qk), v (B,S,H,vh), latent (B,S,cache).
        RoPE applied; latent stores the *rotated* k_rope (decode-ready)."""
        cfg = self.cfg
        m = cfg.mla or MLAConfig()
        b, s, _ = x.shape
        h = cfg.n_heads
        q = (x @ p["w_q"]).reshape(b, s, h, m.qk_dim)
        q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
        q_rope = rope(q_rope, positions, cfg.rope_theta)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)

        ckv = x @ p["w_dkv"]  # (B,S,cache_dim)
        c, k_rope = ckv[..., : m.kv_lora_rank], ckv[..., m.kv_lora_rank :]
        k_rope = rope(k_rope[..., None, :], positions, cfg.rope_theta)[:, :, 0, :]
        latent = jnp.concatenate([c, k_rope], axis=-1)
        k, v = self._mla_expand(p, latent)
        return q, k, v, latent

    def _mla_expand(self, p, latent):
        """latent (..., S, cache_dim) -> k (..., S, H, qk), v (..., S, H, vh)."""
        cfg = self.cfg
        m = cfg.mla or MLAConfig()
        h = cfg.n_heads
        c, k_rope = latent[..., : m.kv_lora_rank], latent[..., m.kv_lora_rank :]
        kv = (c @ p["w_ukv"]).reshape(latent.shape[:-1] + (h, m.qk_nope_dim + m.v_head_dim))
        k_nope, v = kv[..., : m.qk_nope_dim], kv[..., m.qk_nope_dim :]
        k_rope_b = jnp.broadcast_to(
            k_rope[..., None, :], k_nope.shape[:-1] + (m.qk_rope_dim,)
        )
        return jnp.concatenate([k_nope, k_rope_b], axis=-1), v

    def _layer(self, p, x, positions, is_moe: bool):
        cfg = self.cfg
        h = rms_norm(x, p["norm1"])
        if cfg.attn == "mla":
            q, k, v, _ = self._mla_proj(p["attn"], h, positions)
        else:
            q, k, v = self._gqa_proj(p["attn"], h)
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        attn = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
        x = x + (attn.reshape(*x.shape[:2], -1) @ p["attn"]["w_o"]).astype(x.dtype)
        h2 = rms_norm(x, p["norm2"])
        if is_moe:
            y, aux = moe_lib.moe_apply(p["moe"], h2, cfg.moe, self.mesh, self.dp_axes, ff_axes=self.ff_axes)
        else:
            y, aux = swiglu_apply(p["ffn"], h2), jnp.zeros((), jnp.float32)
        return x + y, aux

    def forward(self, params, tokens, positions=None):
        cfg = self.cfg
        b, s = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        x = jnp.take(params["embed"], tokens, axis=0)
        x = self._constrain(x, "batch", None, None)
        aux_total = jnp.zeros((), jnp.float32)

        def scan_stack(x, aux_total, stack, is_moe):
            body = jax.checkpoint(
                lambda xx, pp: self._layer(pp, xx, positions, is_moe),
                policy=jax.checkpoint_policies.nothing_saveable,
            )

            def step(carry, p):
                xx, aux = carry
                xx = self._constrain(xx, "batch", None, None)
                xx, a = body(xx, p)
                return (xx, aux + a), None

            (x, aux_total), _ = jax.lax.scan(step, (x, aux_total), stack)
            return x, aux_total

        if self.n_dense:
            x, aux_total = scan_stack(x, aux_total, params["dense_stack"], False)
        if self.n_moe:
            x, aux_total = scan_stack(x, aux_total, params["moe_stack"], True)
        x = rms_norm(x, params["final_norm"])
        logits = x @ params["out_proj"]
        logits = self._constrain(logits, "batch", None, "vocab")
        return logits, aux_total / max(self.n_moe, 1)

    # ----------------------------------------------------------- train step
    def make_optimizer(self):
        if self.cfg.optimizer == "adafactor":
            return adafactor.init, adafactor.update, adafactor.AdafactorConfig()
        return adamw.init, adamw.update, adamw.AdamWConfig()

    def make_train_step(self):
        cfg = self.cfg
        opt_init, opt_update, opt_cfg = self.make_optimizer()

        def loss_fn(params, batch):
            logits, aux = self.forward(params, batch["tokens"])
            loss = cross_entropy(logits, batch["labels"])
            coef = cfg.moe.router_aux_coef if cfg.moe else 0.0
            return loss + coef * aux, (loss, aux)

        def train_step(params, opt_state, batch):
            (_, (loss, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            new_params, new_opt = opt_update(opt_cfg, grads, opt_state, params)
            return new_params, new_opt, {"loss": loss, "moe_aux": aux}

        return train_step, opt_init

    # ------------------------------------------------------------- prefill
    def make_prefill_step(self):
        """tokens (B, S) -> (last-token logits (B, V), kv cache pytree).
        GQA cache: k/v (L,B,S,G,Dh); MLA cache: latent (L,B,S,cache_dim)."""
        cfg = self.cfg

        def prefill(params, tokens):
            b, s = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
            x = jnp.take(params["embed"], tokens, axis=0)
            x = self._constrain(x, "batch", None, None)

            def step(xx, p, is_moe):
                h = rms_norm(xx, p["norm1"])
                if cfg.attn == "mla":
                    q, k, v, latent = self._mla_proj(p["attn"], h, positions)
                    cache = {"ckv": self._constrain(latent, "batch", "seq_kv", None)}
                else:
                    q, k, v = self._gqa_proj(p["attn"], h)
                    q = rope(q, positions, cfg.rope_theta)
                    k = rope(k, positions, cfg.rope_theta)
                    cache = {
                        "k": self._constrain(k, "batch", "seq_kv", None, None),
                        "v": self._constrain(v, "batch", "seq_kv", None, None),
                    }
                attn = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
                xx = xx + (attn.reshape(b, s, -1) @ p["attn"]["w_o"]).astype(xx.dtype)
                h2 = rms_norm(xx, p["norm2"])
                if is_moe:
                    y, _ = moe_lib.moe_apply(p["moe"], h2, cfg.moe, self.mesh, self.dp_axes, ff_axes=self.ff_axes)
                else:
                    y = swiglu_apply(p["ffn"], h2)
                return xx + y, cache

            def run(stack, x, is_moe):
                body = jax.checkpoint(
                    lambda xx, pp: step(xx, pp, is_moe),
                    policy=jax.checkpoint_policies.nothing_saveable,
                )
                return jax.lax.scan(lambda c, p: body(c, p), x, stack)

            caches = []
            if self.n_dense:
                x, c = run(params["dense_stack"], x, False)
                caches.append(c)
            if self.n_moe:
                x, c = run(params["moe_stack"], x, True)
                caches.append(c)
            cache = jax.tree.map(lambda *cs: jnp.concatenate(cs, axis=0), *caches)
            x = rms_norm(x[:, -1:, :], params["final_norm"])
            logits = (x @ params["out_proj"])[:, 0, :]
            return self._constrain(logits, "batch", "vocab"), cache

        return prefill

    # -------------------------------------------------------------- decode
    def cache_struct(self, batch: int, seq: int):
        cfg = self.cfg
        if cfg.attn == "mla":
            m = cfg.mla or MLAConfig()
            return {
                "ckv": jax.ShapeDtypeStruct((cfg.n_layers, batch, seq, m.cache_dim), cfg.dtype)
            }
        shp = (cfg.n_layers, batch, seq, cfg.n_kv_heads, cfg.dh)
        return {"k": jax.ShapeDtypeStruct(shp, cfg.dtype), "v": jax.ShapeDtypeStruct(shp, cfg.dtype)}

    def cache_logical(self):
        if self.cfg.attn == "mla":
            return {"ckv": (None, "batch", "seq_kv", None)}
        lg = (None, "batch", "seq_kv", None, None)
        return {"k": lg, "v": lg}

    def _seq_shard_index(self):
        idx = jnp.zeros((), jnp.int32)
        for a in self.seq_axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    @staticmethod
    def _write_at(cache, new_row, local_pos, owns):
        """Functionally write new_row (B,1,...) at [:, local_pos] iff owns."""
        old = jax.lax.dynamic_slice_in_dim(cache, local_pos, 1, axis=1)
        mixed = jnp.where(owns, new_row, old)
        return jax.lax.dynamic_update_slice_in_dim(cache, mixed, local_pos, axis=1)

    def _gqa_decode_local(self, q, k_new, v_new, k_cache, v_cache, pos):
        """Shard-local split-K decode. q (B,H,Dh); k_new/v_new (B,G,Dh);
        caches (B,S_loc,G,Dh); pos () int32 absolute position."""
        s_loc = k_cache.shape[1]
        lo = self._seq_shard_index() * s_loc
        local_pos = jnp.clip(pos - lo, 0, s_loc - 1)
        owns = (pos >= lo) & (pos < lo + s_loc)
        k_cache = self._write_at(k_cache, k_new[:, None], local_pos, owns)
        v_cache = self._write_at(v_cache, v_new[:, None], local_pos, owns)

        b, h, dh = q.shape
        g = k_cache.shape[2]
        rep = h // g
        scale = 1.0 / math.sqrt(dh)
        qg = q.reshape(b, g, rep, dh).astype(jnp.float32) * scale
        s = jnp.einsum("bgrd,bsgd->bgrs", qg, k_cache.astype(jnp.float32))
        kv_pos = lo + jnp.arange(s_loc)
        s = jnp.where((kv_pos <= pos)[None, None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bgrs,bsgd->bgrd", p, v_cache.astype(jnp.float32))
        out = flash_combine(o.reshape(b, h, dh), m.reshape(b, h), l.reshape(b, h), self.seq_axes)
        return out.astype(q.dtype), k_cache, v_cache

    def _mla_decode_local(self, q, ckv_new, ckv_cache, w_ukv, pos):
        """q (B,H,qk); ckv_new (B,cache_dim); ckv_cache (B,S_loc,cache_dim)."""
        cfg = self.cfg
        m = cfg.mla or MLAConfig()
        s_loc = ckv_cache.shape[1]
        lo = self._seq_shard_index() * s_loc
        local_pos = jnp.clip(pos - lo, 0, s_loc - 1)
        owns = (pos >= lo) & (pos < lo + s_loc)
        ckv_cache = self._write_at(ckv_cache, ckv_new[:, None], local_pos, owns)

        k, v = self._mla_expand({"w_ukv": w_ukv}, ckv_cache)  # (B,S_loc,H,*)
        b = q.shape[0]
        scale = 1.0 / math.sqrt(m.qk_dim)
        s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32) * scale, k.astype(jnp.float32))
        kv_pos = lo + jnp.arange(s_loc)
        s = jnp.where((kv_pos <= pos)[None, None, :], s, -1e30)
        mx = jnp.max(s, axis=-1)
        p = jnp.exp(s - mx[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
        out = flash_combine(o, mx, l, self.seq_axes)
        return out.astype(q.dtype), ckv_cache

    def make_decode_step(self):
        """(params, cache, token (B,), pos ()) -> (logits (B,V), new cache)."""
        cfg = self.cfg
        mesh = self.mesh
        cache_lg = self.cache_logical()
        batch_spec = logical_to_spec(("batch",), mesh, self.rules)

        if cfg.attn == "mla":
            kv_spec = logical_to_spec(cache_lg["ckv"][1:], mesh, self.rules)
            local = jax.shard_map(
                self._mla_decode_local,
                mesh=mesh,
                in_specs=(batch_spec, batch_spec, kv_spec, P(None, None), P()),
                out_specs=(batch_spec, kv_spec),
                check_vma=False,
            )
        else:
            kv_spec = logical_to_spec(cache_lg["k"][1:], mesh, self.rules)
            local = jax.shard_map(
                self._gqa_decode_local,
                mesh=mesh,
                in_specs=(batch_spec, batch_spec, batch_spec, kv_spec, kv_spec, P()),
                out_specs=(batch_spec, kv_spec, kv_spec),
                check_vma=False,
            )

        def layer_decode(p, x, cache_slice, pos, is_moe):
            b = x.shape[0]
            h = rms_norm(x, p["norm1"])[:, None, :]  # (B,1,d)
            positions = jnp.full((b, 1), pos, jnp.int32)
            if cfg.attn == "mla":
                m = cfg.mla or MLAConfig()
                qd = m.qk_dim
                q = (h @ p["attn"]["w_q"]).reshape(b, 1, cfg.n_heads, qd)
                q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
                q = jnp.concatenate(
                    [q_nope, rope(q_rope, positions, cfg.rope_theta)], axis=-1
                )[:, 0]
                ckv = (h @ p["attn"]["w_dkv"])[:, 0]  # (B,cache_dim)
                c_part = ckv[:, : m.kv_lora_rank]
                r_part = rope(
                    ckv[:, None, None, m.kv_lora_rank :], positions[:, :1], cfg.rope_theta
                )[:, 0, 0]
                ckv_new = jnp.concatenate([c_part, r_part], axis=-1)
                out, new_ckv = local(q, ckv_new, cache_slice["ckv"], p["attn"]["w_ukv"], pos)
                new_cache = {"ckv": new_ckv}
            else:
                q, k, v = self._gqa_proj(p["attn"], h)
                q = rope(q, positions, cfg.rope_theta)[:, 0]
                k = rope(k, positions, cfg.rope_theta)[:, 0]
                out, k_c, v_c = local(q, k, v[:, 0], cache_slice["k"], cache_slice["v"], pos)
                new_cache = {"k": k_c, "v": v_c}
            x = x + (out.reshape(b, -1) @ p["attn"]["w_o"]).astype(x.dtype)
            h2 = rms_norm(x, p["norm2"])
            if is_moe:
                y, _ = moe_lib.moe_apply(p["moe"], h2[:, None, :], cfg.moe, mesh, self.dp_axes, ff_axes=self.ff_axes)
                y = y[:, 0]
            else:
                y = swiglu_apply(p["ffn"], h2)
            return x + y, new_cache

        def decode(params, cache, token, pos):
            x = jnp.take(params["embed"], token, axis=0)  # (B, d)
            x = self._constrain(x, "batch", None)
            chunks = []

            def run(stack, x, n, is_moe, offset):
                sliced = jax.tree.map(
                    lambda c: jax.lax.dynamic_slice_in_dim(c, offset, n, 0), cache
                )

                def stepf(xx, inp):
                    p, csl = inp
                    return layer_decode(p, xx, csl, pos, is_moe)

                return jax.lax.scan(stepf, x, (stack, sliced))

            if self.n_dense:
                x, c = run(params["dense_stack"], x, self.n_dense, False, 0)
                chunks.append(c)
            if self.n_moe:
                x, c = run(params["moe_stack"], x, self.n_moe, True, self.n_dense)
                chunks.append(c)
            new_cache = jax.tree.map(lambda *cs: jnp.concatenate(cs, axis=0), *chunks)
            x = rms_norm(x, params["final_norm"])
            logits = x @ params["out_proj"]
            return self._constrain(logits, "batch", "vocab"), new_cache

        return decode
