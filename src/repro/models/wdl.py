"""Generic Wide-and-Deep-Learning model (paper Fig. 2).

embedding layer (packed, MP) -> feature-interaction modules -> MLP -> logits.
Covers the four assigned recsys archs (deepfm / dcn-v2 / sasrec / mind) and
the paper's own models (W&D / DLRM / DIN / MMoE / CAN) through the
InteractionSpec wiring in the arch config.

The model consumes the *packed group outputs* of the PICASSO engine:
``pooled[gid]: [B, n_bags_g, D_g]`` plus the raw batch (for masks / dense
features) and produces ``logits [B, n_tasks]``.
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import WDLConfig
from repro.core.features import FieldView, field_index
from repro.core.packing import PicassoPlan
from repro.layers import interactions as I
from repro.layers.mlp import init_linear, init_mlp, linear, mlp


class WDLModel:
    def __init__(self, cfg: WDLConfig, plan: PicassoPlan):
        self.cfg = cfg
        self.plan = plan
        self.fidx: Dict[str, FieldView] = field_index(plan)
        self.pooled_fields = [f for f in cfg.fields if f.pooling != "none"]
        self.seq_fields = [f for f in cfg.fields if f.pooling == "none"]
        self._wiring = self._plan_wiring()

    # ------------------------------------------------------------------ views
    def field_emb(self, pooled: Dict[int, jnp.ndarray], name: str) -> jnp.ndarray:
        v = self.fidx[name]
        out = pooled[v.gid][:, v.bag_offset:v.bag_offset + v.n_bags, :]
        return out[:, 0, :] if v.n_bags == 1 and self.cfg.field_by_name(name).pooling != "none" else out

    def field_stack(self, pooled: Dict[int, jnp.ndarray], names: List[str]) -> jnp.ndarray:
        """``[B, len(names), D]``: the pooled (one-bag) fields ``names``, all
        of width D. Fields in consecutive bags of one group come out as one
        slice of ``pooled``, so the backward pads once per run and not once
        per field: DeepFM's 39 per-field ``[B, 39, 10]`` pads would each be
        lane-padded 13x on a TPU and together overflow the chip's memory."""
        runs: List[List[int]] = []
        for n in names:
            v = self.fidx[n]
            if runs and runs[-1][0] == v.gid and runs[-1][2] == v.bag_offset:
                runs[-1][2] += 1
            else:
                runs.append([v.gid, v.bag_offset, v.bag_offset + 1])
        parts = [pooled[g][:, a:z, :] for g, a, z in runs]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

    def field_mask(self, batch: Dict, name: str) -> jnp.ndarray:
        return batch["fields"][name]["weights"] > 0

    # ----------------------------------------------------------------- wiring
    def _plan_wiring(self) -> Dict[str, Any]:
        cfg = self.cfg
        base_dim = sum(f.dim for f in self.pooled_fields)
        dense_dim = cfg.dense_arch[-1] if cfg.dense_arch else cfg.n_dense
        base_dim += dense_dim
        deep_dim = 0
        consumed_base = False
        mmoe_spec = None
        for it in cfg.interactions:
            if it.kind == "linear" or it.kind == "fm":
                continue  # logit-like
            elif it.kind == "cross":
                deep_dim += base_dim
                consumed_base = True
            elif it.kind == "dot":
                dims = [f.dim for f in self.pooled_fields]
                d0 = dims[0]
                nf = sum(1 for d in dims if d == d0) + (1 if dense_dim == d0 else 0)
                deep_dim += nf * (nf - 1) // 2
            elif it.kind == "self_attn_seq":
                d = self.cfg.field_by_name(it.fields[0]).dim
                deep_dim += 3 * d
            elif it.kind == "target_attn":
                hists = [f for f in it.fields if self.cfg.field_by_name(f).pooling == "none"]
                d = self.cfg.field_by_name(hists[0]).dim
                deep_dim += len(hists) * d
            elif it.kind == "capsule":
                d = self.cfg.field_by_name(it.fields[0]).dim
                deep_dim += 2 * d
            elif it.kind == "gru":
                deep_dim += self.cfg.field_by_name(it.fields[0]).dim
            elif it.kind == "coaction":
                deep_dim += it.kwargs.get("layers", (4, 4))[-1]
            elif it.kind == "mmoe":
                mmoe_spec = it
            else:
                raise ValueError(f"unknown interaction {it.kind}")
        if not consumed_base:
            deep_dim += base_dim
        return {"base_dim": base_dim, "dense_dim": dense_dim, "deep_dim": deep_dim,
                "mmoe": mmoe_spec, "consumed_base": consumed_base}

    # ------------------------------------------------------------------- init
    def init_dense(self, key: jax.Array) -> Dict:
        cfg, w = self.cfg, self._wiring
        params: Dict[str, Any] = {}
        key, *ks = jax.random.split(key, len(cfg.interactions) + 2)
        ki = iter(ks)
        if cfg.dense_arch:
            params["bottom"] = init_mlp(next(ki), cfg.n_dense, cfg.dense_arch)
        for n, it in enumerate(cfg.interactions):
            name = f"i{n}_{it.kind}"
            if it.kind == "linear":
                k = next(ki)
                params[name] = {
                    f.name: jax.random.normal(jax.random.fold_in(k, i), (f.dim, 1)) * 0.01
                    for i, f in enumerate(self.pooled_fields)}
            elif it.kind == "cross":
                params[name] = I.init_cross(next(ki), w["base_dim"], it.kwargs.get("n_layers", 3))
            elif it.kind == "self_attn_seq":
                d = cfg.field_by_name(it.fields[0]).dim
                params[name] = I.init_self_attn_seq(next(ki), d, it.kwargs.get("n_blocks", 2),
                                                    it.kwargs.get("n_heads", 1))
            elif it.kind == "target_attn":
                d = cfg.field_by_name(it.fields[0]).dim
                params[name] = I.init_target_attn(next(ki), d)
            elif it.kind == "capsule":
                d = cfg.field_by_name(it.fields[0]).dim
                params[name] = I.init_capsule(next(ki), d, it.kwargs.get("n_interests", 4))
            elif it.kind == "gru":
                d = cfg.field_by_name(it.fields[0]).dim
                params[name] = I.init_gru(next(ki), d)
            elif it.kind == "mmoe":
                params[name] = I.init_mmoe(next(ki), w["deep_dim"],
                                           it.kwargs.get("n_experts", 4),
                                           it.kwargs.get("expert_dim", 64),
                                           cfg.n_tasks)
        if w["mmoe"] is not None:
            ed = w["mmoe"].kwargs.get("expert_dim", 64)
            for t in range(cfg.n_tasks):
                key, k2 = jax.random.split(key)
                params[f"task{t}"] = init_mlp(k2, ed, tuple(cfg.mlp_dims) + (1,))
        else:
            key, k2 = jax.random.split(key)
            params["top"] = init_mlp(k2, w["deep_dim"], tuple(cfg.mlp_dims) + (cfg.n_tasks,))
        return params

    # ------------------------------------------------------------------ apply
    def apply(self, params: Dict, pooled: Dict[int, jnp.ndarray], batch: Dict) -> jnp.ndarray:
        cfg, w = self.cfg, self._wiring
        b = next(iter(pooled.values())).shape[0]

        dense_proc = None
        if cfg.n_dense > 0:
            dx = batch["dense"]
            dense_proc = mlp(params["bottom"], dx) if cfg.dense_arch else dx

        by_dim: Dict[int, List[str]] = {}
        for f in self.pooled_fields:
            by_dim.setdefault(f.dim, []).append(f.name)
        # runs of equal width keep field order in the concatenated base
        base_parts = [self.field_stack(pooled, [f.name for f in fs]).reshape(b, -1)
                      for _, fs in itertools.groupby(self.pooled_fields,
                                                     key=lambda f: f.dim)]
        if dense_proc is not None:
            base_parts.append(dense_proc)
        base = jnp.concatenate(base_parts, axis=-1) if base_parts else jnp.zeros((b, 0))

        wide_logit = jnp.zeros((b, 1))
        deep_parts: List[jnp.ndarray] = []

        for n, it in enumerate(cfg.interactions):
            name = f"i{n}_{it.kind}"
            if it.kind == "linear":
                for names in by_dim.values():
                    wt = jnp.stack([params[name][fn] for fn in names])  # [F, D, 1]
                    wide_logit = wide_logit + jnp.einsum(
                        "bfd,fdo->bo", self.field_stack(pooled, names), wt)
            elif it.kind == "fm":
                for names in by_dim.values():
                    if len(names) > 1:
                        wide_logit = wide_logit + I.fm_interaction(
                            self.field_stack(pooled, names))
            elif it.kind == "dot":
                d0 = self.pooled_fields[0].dim
                es = self.field_stack(pooled, [f.name for f in self.pooled_fields
                                               if f.dim == d0])
                if dense_proc is not None and dense_proc.shape[-1] == d0:
                    es = jnp.concatenate([es, dense_proc[:, None, :]], axis=1)
                deep_parts.append(I.dot_interaction(es))
            elif it.kind == "cross":
                deep_parts.append(I.cross_net(params[name], base))
            elif it.kind == "self_attn_seq":
                hist_f, pos_f, tgt_f = it.fields
                seq = self.field_emb(pooled, hist_f) + self.field_emb(pooled, pos_f)
                mask = self.field_mask(batch, hist_f)
                repr_ = I.self_attn_seq(params[name], seq, mask,
                                        n_heads=it.kwargs.get("n_heads", 1))
                tgt = self.field_emb(pooled, tgt_f)
                wide_logit = wide_logit + jnp.sum(repr_ * tgt, axis=-1, keepdims=True)
                deep_parts += [repr_, tgt, repr_ * tgt]
            elif it.kind == "target_attn":
                tgt_name = it.fields[-1]
                tgt = self.field_emb(pooled, tgt_name)
                for fn in it.fields[:-1]:
                    hist = self.field_emb(pooled, fn)
                    deep_parts.append(I.target_attn(params[name], hist, tgt, self.field_mask(batch, fn)))
            elif it.kind == "capsule":
                hist_f, tgt_f = it.fields
                hist = self.field_emb(pooled, hist_f)
                tgt = self.field_emb(pooled, tgt_f)
                caps = I.capsule_routing(params[name], hist, self.field_mask(batch, hist_f),
                                         it.kwargs.get("routing_iters", 3),
                                         jax.random.PRNGKey(17),
                                         n_interests=it.kwargs.get("n_interests", 4))
                deep_parts += [I.label_aware_attn(caps, tgt), tgt]
            elif it.kind == "gru":
                fn = it.fields[0]
                deep_parts.append(I.gru(params[name], self.field_emb(pooled, fn),
                                        self.field_mask(batch, fn)))
            elif it.kind == "coaction":
                hist_f, tgt_f = it.fields
                deep_parts.append(I.coaction(self.field_emb(pooled, hist_f),
                                             self.field_emb(pooled, tgt_f),
                                             self.field_mask(batch, hist_f),
                                             it.kwargs.get("layers", (4, 4))))
            elif it.kind == "mmoe":
                pass  # handled below

        if not w["consumed_base"]:
            deep_parts = [base] + deep_parts
        deep_in = jnp.concatenate(deep_parts, axis=-1)

        if w["mmoe"] is not None:
            n = list(cfg.interactions).index(w["mmoe"])
            towers = I.mmoe(params[f"i{n}_mmoe"], deep_in)
            logits = jnp.concatenate(
                [mlp(params[f"task{t}"], towers[t], final_act=False) for t in range(cfg.n_tasks)],
                axis=-1)
        else:
            logits = mlp(params["top"], deep_in, final_act=False)
        return logits + wide_logit

    # -------------------------------------------------------------- retrieval
    def user_repr(self, params: Dict, pooled: Dict[int, jnp.ndarray], batch: Dict
                  ) -> jnp.ndarray:
        """User-tower vectors [K, D] for two-tower retrieval (K>1: MIND)."""
        cfg = self.cfg
        for n, it in enumerate(cfg.interactions):
            name = f"i{n}_{it.kind}"
            if it.kind == "self_attn_seq":
                hist_f, pos_f, _ = it.fields
                seq = self.field_emb(pooled, hist_f) + self.field_emb(pooled, pos_f)
                r = I.self_attn_seq(params[name], seq, self.field_mask(batch, hist_f),
                                    n_heads=it.kwargs.get("n_heads", 1))
                return r  # [1, D]
            if it.kind == "capsule":
                hist_f, _ = it.fields
                hist = self.field_emb(pooled, hist_f)
                caps = I.capsule_routing(params[name], hist,
                                         self.field_mask(batch, hist_f),
                                         it.kwargs.get("routing_iters", 3),
                                         jax.random.PRNGKey(17),
                                         n_interests=it.kwargs.get("n_interests", 4))
                return caps[0]  # [K, D]
        # CTR fallback: mean of pooled embeddings of the first dim-group
        embs = [self.field_emb(pooled, f.name) for f in self.pooled_fields]
        return jnp.mean(jnp.stack(embs, 0), 0)

    # ------------------------------------------------------------------- loss
    def loss(self, params: Dict, pooled: Dict[int, jnp.ndarray], batch: Dict
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        logits = self.apply(params, pooled, batch)
        labels = batch["labels"]
        if labels.ndim == 1:
            labels = labels[:, None]
        labels = jnp.broadcast_to(labels, logits.shape).astype(logits.dtype)
        ls = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
        return ls.sum(), logits
