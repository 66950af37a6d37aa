"""Decoder-only transformer LM covering the dense / moe / vlm families.

Supports: GQA (+qk-norm, +QKV-bias), RoPE, SwiGLU FFN, sliding-window
attention (Mixtral), MoE FFN (dispatch / expert-parallel), VLM prefix
(precomputed patch embeddings — frontend stub per assignment), scan-over-
layers with optional remat, ring-buffer KV caches for serving.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from . import layers as L
from . import moe as M
from .sharding import MeshPlan, activation_spec, build_param_specs

AUX_LOSS_WEIGHT = 0.01


class DecoderLM:
    """Functional decoder LM; all state lives in explicit pytrees."""

    def __init__(self, cfg: ModelConfig, run: RunConfig | None = None,
                 mesh: Mesh | None = None, plan: MeshPlan | None = None):
        self.cfg = cfg
        self.run = run or RunConfig()
        self.mesh = mesh
        self.plan = plan or MeshPlan()
        self.dtype = jnp.dtype(cfg.param_dtype)
        self.adtype = jnp.dtype(cfg.activation_dtype)

    # ------------------------------------------------------------- helpers

    def _constrain(self, x, spec: P):
        if self.mesh is not None:
            return lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec))
        return x

    @property
    def _n_moe_layers(self) -> int:
        if self.cfg.moe is None:
            return 0
        return self.cfg.n_layers - self.cfg.moe.first_k_dense

    @property
    def _n_dense_layers(self) -> int:
        if self.cfg.moe is None:
            return self.cfg.n_layers
        return self.cfg.moe.first_k_dense

    # ---------------------------------------------------------------- init

    def _dense_block_init(self, key):
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 2)
        return {
            "attn_norm": L.rmsnorm_init(cfg.d_model, dt),
            "attn": L.mha_init(ks[0], cfg, dt),
            "ffn_norm": L.rmsnorm_init(cfg.d_model, dt),
            "ffn": L.swiglu_init(ks[1], cfg.d_model, cfg.d_ff, dt),
        }

    def _moe_block_init(self, key):
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 2)
        return {
            "attn_norm": L.rmsnorm_init(cfg.d_model, dt),
            "attn": L.mha_init(ks[0], cfg, dt),
            "ffn_norm": L.rmsnorm_init(cfg.d_model, dt),
            "moe": M.moe_init(ks[1], cfg, dt),
        }

    def init(self, key):
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 4)
        params = {"embed": L.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt),
                  "final_norm": L.rmsnorm_init(cfg.d_model, dt)}
        if not cfg.tie_embeddings:
            params["unembed"] = L.dense_init(
                ks[1], (cfg.d_model, cfg.vocab_size), dt)
        if self._n_dense_layers and cfg.moe is not None:
            params["dense_layers"] = L.stack_layer_params(
                self._dense_block_init, ks[2], self._n_dense_layers)
        if cfg.moe is not None:
            params["layers"] = L.stack_layer_params(
                self._moe_block_init, ks[3], self._n_moe_layers)
        else:
            params["layers"] = L.stack_layer_params(
                self._dense_block_init, ks[3], cfg.n_layers)
        return params

    def param_shapes(self):
        return jax.eval_shape(
            lambda: self.init(jax.random.PRNGKey(0)))

    def param_specs(self):
        return build_param_specs(self.param_shapes(), self.plan, self.mesh)

    def param_count(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(self.param_shapes()))

    def active_param_count(self) -> int:
        total = self.param_count()
        if self.cfg.moe is None:
            return total
        shapes = self.param_shapes()
        expert = sum(
            int(np.prod(l.shape)) for l in
            jax.tree.leaves(shapes["layers"]["moe"]["experts"]))
        m = self.cfg.moe
        return total - expert + int(expert * m.top_k / m.n_experts)

    # ------------------------------------------------------------- blocks

    def _ffn_apply(self, p, x, *, decode: bool = False):
        """Returns (y, aux_loss)."""
        if "ffn" in p:
            return L.swiglu(p["ffn"], x), jnp.zeros((), jnp.float32)
        # MoE
        S = x.shape[1]
        divisible = (self.mesh is not None and not decode
                     and x.shape[0] % self._dp_size() == 0
                     and self._ep_size() > 1)
        use_ep = (divisible and self.run.ep_moe
                  and S % self._ep_size() == 0
                  and self.cfg.moe.n_experts % self._ep_size() == 0)
        if use_ep:
            return self._moe_ep(p["moe"], x)
        if divisible and self.run.moe_tp_f and not self.plan.sp:
            return self._moe_tp_f(p["moe"], x)
        return M.moe_ffn_dispatch(p["moe"], x, self.cfg)

    def _ep_size(self) -> int:
        return self.mesh.shape[self.plan.ep] if self.mesh else 1

    def _dp_size(self) -> int:
        if not self.mesh:
            return 1
        n = 1
        for a in (self.plan.batch if isinstance(self.plan.batch, tuple)
                  else (self.plan.batch,)):
            n *= self.mesh.shape[a]
        return n

    def _moe_ep(self, p, x):
        """shard_map-wrapped expert-parallel MoE (DESIGN.md §3.1).

        Two FSDP treatments of the expert weights:
        * default (ZeRO-3): weights sharded on the fsdp axis along d_model,
          all-gathered per use;
        * weight-stationary (run.moe_weight_stationary): weights sharded
          along the FFN-hidden dim, never gathered — the down-projection's
          partial sums are psum'd instead (activation bytes << weight
          bytes for large experts; §Perf hillclimb)."""
        plan = self.plan
        dp = plan.batch_axes
        ws = self.run.moe_weight_stationary and plan.fsdp is not None
        x_spec = P(dp, plan.ep, None)             # tokens: B over dp, S over ep
        if ws:
            expert_spec = {
                "w_gate": P(plan.ep, None, plan.fsdp),
                "w_up": P(plan.ep, None, plan.fsdp),
                "w_down": P(plan.ep, plan.fsdp, None),
            }
        else:
            expert_spec = {
                "w_gate": P(plan.ep, plan.fsdp, None),
                "w_up": P(plan.ep, plan.fsdp, None),
                "w_down": P(plan.ep, None, plan.fsdp),
            }
        p_specs = {"router": P(None, None), "experts": expert_spec}
        if "shared" in p:
            p_specs["shared"] = {k: P(None, None) for k in p["shared"]}

        fsdp = plan.fsdp

        def body(pp, xx):
            if fsdp is not None and not ws:
                # ZeRO-3: gather sharded expert weights before the GEMMs
                pp = dict(pp)
                pp["experts"] = {
                    "w_gate": lax.all_gather(pp["experts"]["w_gate"], fsdp,
                                             axis=1, tiled=True),
                    "w_up": lax.all_gather(pp["experts"]["w_up"], fsdp,
                                           axis=1, tiled=True),
                    "w_down": lax.all_gather(pp["experts"]["w_down"], fsdp,
                                             axis=2, tiled=True),
                }
            y, aux = M.moe_ffn_ep(pp, xx, self.cfg, plan.ep,
                                  partial_ffn_axis=fsdp if ws else None)
            aux = lax.pmean(aux, plan.batch_axes)
            return y, aux

        y, aux = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(p_specs, x_spec),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(p, x)
        return y, aux

    def _moe_tp_f(self, p, x):
        """shard_map-wrapped TP-f MoE for few-expert archs (Mixtral)."""
        plan = self.plan
        dp = plan.batch_axes
        x_spec = P(dp, None, None)      # tokens replicated across tp
        p_specs = {
            "router": P(None, None),
            "experts": {
                "w_gate": P(None, plan.fsdp, plan.tp),
                "w_up": P(None, plan.fsdp, plan.tp),
                "w_down": P(None, plan.tp, plan.fsdp),
            },
        }
        if "shared" in p:
            p_specs["shared"] = {k: P(None, None) for k in p["shared"]}

        def body(pp, xx):
            y, aux = M.moe_ffn_tp_f(pp, xx, self.cfg, plan.tp,
                                    fsdp_axis=plan.fsdp)
            return y, lax.pmean(aux, plan.batch_axes)

        return jax.shard_map(
            body, mesh=self.mesh, in_specs=(p_specs, x_spec),
            out_specs=(x_spec, P()), check_vma=False)(p, x)

    def _block(self, p, x, positions, *, window):
        h = L.rmsnorm(p["attn_norm"], x, self.cfg.norm_eps)
        h = L.self_attention(p["attn"], h, self.cfg, positions,
                             causal=True, window=window)
        x = x + h
        h = L.rmsnorm(p["ffn_norm"], x, self.cfg.norm_eps)
        h, aux = self._ffn_apply(p, h)
        x = x + h
        x = self._constrain(x, activation_spec(self.plan))
        return x, aux

    def _block_decode(self, p, x, cache, pos, *, window):
        h = L.rmsnorm(p["attn_norm"], x, self.cfg.norm_eps)
        h, cache = L.self_attention_decode(p["attn"], h, self.cfg, cache, pos,
                                           window=window)
        x = x + h
        h = L.rmsnorm(p["ffn_norm"], x, self.cfg.norm_eps)
        h, _ = self._ffn_apply(p, h, decode=True)
        return x + h, cache

    def _scan_blocks(self, stacked, x, positions, *, window):
        block = functools.partial(self._block, window=window)
        if self.run.remat != "none":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if self.run.remat == "dots" else None)
            block = jax.checkpoint(block, policy=policy)

        def body(carry, lp):
            xx, aux = carry
            xx, a = block(lp, xx, positions)
            return (xx, aux + a), None

        (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), stacked)
        return x, aux

    # ------------------------------------------------------------ forward

    def _embed_tokens(self, params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.adtype)
        return x

    def _assemble_input(self, params, tokens, img_embeds=None):
        x = self._embed_tokens(params, tokens)
        if self.cfg.vlm is not None:
            if img_embeds is None:
                raise ValueError("vlm model requires img_embeds")
            x = jnp.concatenate([img_embeds.astype(self.adtype), x], axis=1)
        return x

    def forward(self, params, tokens, img_embeds=None):
        """Training/prefill forward over the full sequence -> logits (B,S,V).

        For VLM the returned logits cover only the text positions."""
        cfg = self.cfg
        x = self._assemble_input(params, tokens, img_embeds)
        B, S, _ = x.shape
        x = self._constrain(x, activation_spec(self.plan))
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        aux = jnp.zeros((), jnp.float32)
        if "dense_layers" in params:
            x, a = self._scan_blocks(params["dense_layers"], x, positions,
                                     window=cfg.sliding_window)
            aux += a
        x, a = self._scan_blocks(params["layers"], x, positions,
                                 window=cfg.sliding_window)
        aux += a
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.vlm is not None:
            x = x[:, self.cfg.vlm.n_image_tokens:]
        logits = self._unembed(params, x)
        return logits, aux

    def _unembed(self, params, x):
        w = params["embed"].T if self.cfg.tie_embeddings else params["unembed"]
        logits = (x @ w).astype(jnp.dtype(self.cfg.logits_dtype))
        return logits

    def loss(self, params, batch):
        logits, aux = self.forward(params, batch["tokens"],
                                   batch.get("img_embeds"))
        ce = L.cross_entropy_loss(logits, batch["labels"],
                                  batch.get("valid"))
        total = ce + AUX_LOSS_WEIGHT * aux
        return total, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------ serving

    def cache_capacity(self, max_len: int) -> int:
        if self.cfg.sliding_window is not None:
            return min(max_len, self.cfg.sliding_window)
        return max_len

    def init_cache(self, batch: int, max_len: int):
        cap = self.cache_capacity(max_len)
        nl = self.cfg.n_layers if self.cfg.moe is None else self._n_moe_layers
        caches = {"layers": L.make_kv_cache(self.cfg, batch, cap, self.adtype,
                                            n_layers=nl),
                  "pos": jnp.zeros((), jnp.int32)}
        if self.cfg.moe is not None and self._n_dense_layers:
            caches["dense_layers"] = L.make_kv_cache(
                self.cfg, batch, cap, self.adtype,
                n_layers=self._n_dense_layers)
        return caches

    def prefill(self, params, tokens, img_embeds=None, max_len: int | None = None):
        """Run the prompt, build decode caches; returns (last_logits, caches)."""
        cfg = self.cfg
        x = self._assemble_input(params, tokens, img_embeds)
        B, S, _ = x.shape
        max_len = max_len or S
        cap = self.cache_capacity(max_len)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        def prefill_block(p, xx):
            h = L.rmsnorm(p["attn_norm"], xx, cfg.norm_eps)
            q, k, v = L.mha_project_qkv(p["attn"], h, cfg, positions)
            o = L.attention(q, k, v, positions, positions, causal=True,
                            window=cfg.sliding_window)
            xx = xx + L.mha_out(p["attn"], o, B, S)
            h = L.rmsnorm(p["ffn_norm"], xx, cfg.norm_eps)
            h, _ = self._ffn_apply(p, h)
            cache = L.make_kv_cache(cfg, B, cap, self.adtype)
            cache = L.cache_write_prefill(cache, k, v)
            return xx + h, cache

        def body(xx, lp):
            xx, cache = prefill_block(lp, xx)
            return xx, cache

        caches = {}
        if "dense_layers" in params:
            x, caches["dense_layers"] = lax.scan(body, x,
                                                 params["dense_layers"])
        x, caches["layers"] = lax.scan(body, x, params["layers"])
        caches["pos"] = jnp.asarray(S, jnp.int32)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = self._unembed(params, x[:, -1:])[:, 0]
        return logits, caches

    def decode_step(self, params, token, caches):
        """token (B,1) int32 -> (logits (B,V), new caches)."""
        cfg = self.cfg
        x = self._embed_tokens(params, token)
        pos = caches["pos"]
        window = cfg.sliding_window

        def body(xx, layer):
            lp, cache = layer
            xx, cache = self._block_decode(lp, xx, cache, pos, window=window)
            return xx, cache

        new = dict(caches)
        if "dense_layers" in params:
            x, new["dense_layers"] = lax.scan(
                body, x, (params["dense_layers"], caches["dense_layers"]))
        x, new["layers"] = lax.scan(
            body, x, (params["layers"], caches["layers"]))
        new["pos"] = pos + 1
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = self._unembed(params, x)[:, 0]
        return logits, new

    def cache_specs(self, batch: int, max_len: int):
        """PartitionSpec tree matching init_cache (for decode in_shardings)."""
        from .sharding import kv_cache_specs
        cap = self.cache_capacity(max_len)
        layer = kv_cache_specs(self.plan, self.mesh, batch, cap,
                               self.cfg.n_kv_heads)
        out = {"layers": dict(layer), "pos": P()}
        if self.cfg.moe is not None and self._n_dense_layers:
            out["dense_layers"] = dict(layer)
        return out

    # -------------------------------------------------------- input specs

    def input_specs(self, shape: ShapeConfig) -> dict:
        """ShapeDtypeStruct stand-ins for every model input (dry-run)."""
        B, S = shape.global_batch, shape.seq_len
        cfg = self.cfg
        f32 = jnp.float32
        if shape.kind == "train":
            n_img = cfg.vlm.n_image_tokens if cfg.vlm else 0
            d = {"tokens": jax.ShapeDtypeStruct((B, S - n_img), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((B, S - n_img), jnp.int32)}
            if cfg.vlm:
                d["img_embeds"] = jax.ShapeDtypeStruct(
                    (B, n_img, cfg.d_model), jnp.dtype(cfg.activation_dtype))
            return d
        if shape.kind == "prefill":
            n_img = cfg.vlm.n_image_tokens if cfg.vlm else 0
            d = {"tokens": jax.ShapeDtypeStruct((B, S - n_img), jnp.int32)}
            if cfg.vlm:
                d["img_embeds"] = jax.ShapeDtypeStruct(
                    (B, n_img, cfg.d_model), jnp.dtype(cfg.activation_dtype))
            return d
        # decode: one token with a cache of seq_len
        caches = jax.eval_shape(lambda: self.init_cache(B, S))
        return {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                "caches": caches}
