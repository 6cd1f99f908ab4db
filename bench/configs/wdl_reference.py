"""Plain reference of the wide-and-deep models these configurations run:
DeepFM (FM + linear + MLP over the field embeddings) and DCN-v2 (stacked
full-rank cross network, then the MLP), trained with Adam on the dense
parameters and row-wise Adagrad on the embedding rows.

Straight ``jax.numpy`` in the precision asked for (float32 at ``highest``
matmul precision for the reference, lower for the control), no kernels, no
packing, no cache tier, no sharding: one table per field, indexed by the
field's hashed id. It imports nothing of the program and takes none of its
state. The weights come from ``bench/weights.py`` and the seed. The only
thing shared with the program is the model's definition, written out here:

* Hashing trick. A raw id ``i`` of field ``f`` reads row
  ``((i * a_f + s_f) mod 2**32) mod vocab_f``, where ``a_f`` is the first
  number coprime with ``vocab_f`` from ``2654435761 mod vocab_f`` up and
  ``s_f = crc32(f) mod 10007``. Two ids may share a row, as in any hashed
  embedding.
* Model. ``x_f`` is field f's row (one id per field, sum pooling), ``X`` the
  ``[B, F, d]`` stack in configuration order, ``base`` its ``[B, F*d]``
  flattening followed by the numeric features. ``fm`` adds
  ``0.5 * sum_d((sum_f x)^2 - sum_f x^2)`` to the logit, ``linear`` adds
  ``sum_f x_f @ W_f``; ``cross`` replaces the MLP input with
  ``x_{l+1} = base * (x_l @ W_l + b_l) + x_l`` (``x_0 = base``). The MLP
  has ReLU between layers and one output. The loss is the mean binary
  cross-entropy with logits over the global batch.
* Updates. Adam (b1 0.9, b2 0.999, eps 1e-8) on every dense leaf; on each
  table the gradients of a step's duplicate rows are summed and a touched
  row takes ``acc += mean_d(g^2)``, ``w -= lr * g / sqrt(acc + eps)``.

``readings`` follows the first steps of a run and returns what the
correctness check compares: the loss of each of the first three steps, each
leaf's norm of the first gradient and of its change after the third step,
and each table's norm of its change after the last step followed.
"""
from __future__ import annotations

import json
import math
import zlib
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_KNUTH = 2654435761
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
ADAGRAD_EPS = 1e-8
EMB_STREAM0, DENSE_STREAM0 = 0, 1_000_000


# ------------------------------------------------------------------ model
def fields(cfg: Dict) -> List[Tuple[str, int, int]]:
    """``(name, vocab, dim)`` of every field, in configuration order."""
    d = cfg["embed_dim"]
    out = [(f"cat_{i}", int(v), d) for i, v in enumerate(cfg["categorical_vocab"])]
    out += [(f"numb_{i}", int(cfg["numeric_buckets"]), d)
            for i in range(cfg.get("numeric_bucket_fields", 0))]
    return out


def _coprime_mult(vocab: int) -> int:
    a = _KNUTH % vocab or 1
    while math.gcd(a, vocab) != 1:
        a += 1
    return a


def hashed_rows(name: str, vocab: int, ids: np.ndarray) -> np.ndarray:
    """Row of each raw id in field ``name``'s table (the hashing trick)."""
    a = _coprime_mult(vocab)
    salt = zlib.crc32(name.encode()) % 10007
    x = (ids.astype(np.uint64) * np.uint64(a) + np.uint64(salt)) & np.uint64(0xFFFFFFFF)
    return (x % np.uint64(vocab)).astype(np.int64)


def emb_std(dim: int) -> float:
    return 1.0 / math.sqrt(dim)


def dense_spec(cfg: Dict) -> List[Tuple[Tuple[str, ...], Tuple[int, ...], float]]:
    """Every dense leaf as ``(path, shape, init std)``; the path names match
    the program's parameter tree, std 0 means zeros."""
    fs = fields(cfg)
    base = sum(d for _, _, d in fs) + cfg.get("n_dense", 0)
    spec = []
    deep_in = base
    for n, it in enumerate(cfg["interactions"]):
        kind = it["kind"]
        if kind == "linear":
            spec += [((f"i{n}_linear", name), (d, 1), 0.01) for name, _, d in fs]
        elif kind == "cross":
            for l in range(it["n_layers"]):
                spec += [((f"i{n}_cross", f"l{l}", "w"), (base, base), 1 / math.sqrt(base)),
                         ((f"i{n}_cross", f"l{l}", "b"), (base,), 0.0)]
        elif kind != "fm":
            raise ValueError(f"the reference has no interaction {kind!r}")
    dims = [deep_in] + list(cfg["mlp_dims"]) + [1]
    for l in range(len(dims) - 1):
        spec += [(("top", f"l{l}", "w"), (dims[l], dims[l + 1]), math.sqrt(2.0 / dims[l])),
                 (("top", f"l{l}", "b"), (dims[l + 1],), 0.0)]
    return spec


def init_dense(cfg: Dict, seed: int, dtype=jnp.float32) -> Dict:
    from bench import weights
    out: Dict = {}
    for k, (path, shape, std) in enumerate(dense_spec(cfg)):
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = weights.leaf(seed, DENSE_STREAM0 + k, shape, std, dtype)
    return out


def logits(cfg: Dict, params: Dict, x: jnp.ndarray, dense_x) -> jnp.ndarray:
    """``x``: ``[B, F, d]`` field rows; ``dense_x``: ``[B, n_dense]`` or None."""
    b = x.shape[0]
    base = x.reshape(b, -1)
    if dense_x is not None:
        base = jnp.concatenate([base, dense_x.astype(x.dtype)], axis=-1)
    wide = jnp.zeros((b, 1), x.dtype)
    deep = base
    for n, it in enumerate(cfg["interactions"]):
        if it["kind"] == "fm":
            s = x.sum(axis=1)
            wide = wide + 0.5 * (s * s - (x * x).sum(axis=1)).sum(-1, keepdims=True)
        elif it["kind"] == "linear":
            p = params[f"i{n}_linear"]
            w = jnp.stack([p[name] for name, _, _ in fields(cfg)])  # [F, d, 1]
            wide = wide + jnp.einsum("bfd,fdo->bo", x, w)
        elif it["kind"] == "cross":
            p = params[f"i{n}_cross"]
            h = base
            for l in range(it["n_layers"]):
                h = base * (h @ p[f"l{l}"]["w"] + p[f"l{l}"]["b"]) + h
            deep = h
    top = params["top"]
    n_top = len(top)
    for l in range(n_top):
        deep = deep @ top[f"l{l}"]["w"] + top[f"l{l}"]["b"]
        if l < n_top - 1:
            deep = jax.nn.relu(deep)
    return deep + wide


def mean_loss(cfg, params, table, idx, dense_x, labels):
    """``table``: every field's touched rows, one after another, ``[R, d]``;
    ``idx``: ``[B, F]`` rows of it."""
    z = logits(cfg, params, table[idx], dense_x).astype(jnp.float32)[:, 0]
    y = labels.astype(jnp.float32)
    ls = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    return ls.sum() / z.shape[0]


# ------------------------------------------------------------ one step
def _step(cfg, params, opt, table, acc, idx, dense_x, labels):
    loss, (g_p, g_t) = jax.value_and_grad(mean_loss, argnums=(1, 2))(
        cfg, params, table, idx, dense_x, labels)
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, opt["m"], g_p)
    v = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, opt["v"], g_p)
    c1 = 1 - ADAM_B1 ** t.astype(jnp.float32)
    c2 = 1 - ADAM_B2 ** t.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, m, v: (p - cfg["lr_dense"] * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS)
                         ).astype(p.dtype), params, m, v)
    # the gradient of a row is the sum over its duplicates in the step; an
    # untouched row has a zero gradient and keeps its value and accumulator
    acc = acc + jnp.mean(g_t * g_t, axis=-1)
    table = (table - cfg["lr_emb"] * g_t / jnp.sqrt(acc + ADAGRAD_EPS)[:, None]
             ).astype(table.dtype)
    return loss, g_p, g_t, params, {"m": m, "v": v, "t": t}, table, acc


def _norms(tree) -> Dict[str, jnp.ndarray]:
    return {"/".join(str(getattr(p, "key", p)) for p in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


_PROGRAMS: Dict[Tuple, object] = {}


def _program(cfg: Dict, dtype, compared: int):
    """One jitted program that follows the steps from the seed: it makes
    the touched rows and the dense leaves, runs the first ``compared`` steps
    one by one and the rest in a loop, and returns the losses and norms. Its
    inputs carry the seed, so it compiles once for a traffic's row count."""
    key = (json.dumps(cfg, sort_keys=True), jnp.dtype(dtype).name, compared)
    if key in _PROGRAMS:
        return _PROGRAMS[key]
    from bench import weights
    fs = fields(cfg)
    names = [f[0] for f in fs]
    d = fs[0][2]
    if any(f[2] != d for f in fs):
        raise ValueError("the reference keeps one table of rows for fields of one width")

    def field_norms(x, field_of):
        sq = jax.ops.segment_sum(jnp.sum(jnp.square(x.astype(jnp.float32)), -1),
                                 field_of, num_segments=len(fs))
        return {f"emb/{n}": v for n, v in zip(names, jnp.sqrt(sq))}

    def run(seed, rows, stream, field_of, idx, dense_x, labels):
        emb0 = weights.table_rows(seed, stream, rows, d, emb_std(d))
        params0 = init_dense(cfg, seed)
        table = emb0.astype(dtype)
        params = jax.tree.map(lambda p: p.astype(dtype), params0)
        opt = {"m": jax.tree.map(jnp.zeros_like, params),
               "v": jax.tree.map(jnp.zeros_like, params),
               "t": jnp.zeros((), jnp.int32)}
        acc = jnp.zeros((table.shape[0],), dtype)

        def dx(s):
            return None if dense_x is None else dense_x[s].astype(dtype)

        def change():
            delta = jax.tree.map(lambda p, p0: p.astype(jnp.float32) - p0, params, params0)
            return {**field_norms(table.astype(jnp.float32) - emb0, field_of),
                    **{f"dense/{k}": v for k, v in _norms(delta).items()}}

        losses, grad1 = [], None
        for s in range(compared):
            loss, g_p, g_t, params, opt, table, acc = _step(
                cfg, params, opt, table, acc, idx[s], dx(s), labels[s])
            losses.append(loss)
            if s == 0:
                grad1 = {**field_norms(g_t, field_of),
                         **{f"dense/{k}": v for k, v in _norms(g_p).items()}}
        change_c = change()

        def later(carry, s):
            params, opt, table, acc = carry
            _, _, _, params, opt, table, acc = _step(
                cfg, params, opt, table, acc, idx[s], dx(s), labels[s])
            return (params, opt, table, acc), None

        (params, opt, table, acc), _ = lax.scan(
            later, (params, opt, table, acc), jnp.arange(compared, labels.shape[0]))
        tables = field_norms(table.astype(jnp.float32) - emb0, field_of)
        return jnp.stack(losses), grad1, change_c, tables

    _PROGRAMS[key] = jax.jit(run)
    return _PROGRAMS[key]


def readings(cfg: Dict, seed: int, batches: Sequence[Dict], dtype=jnp.float32,
             compared: int = 3) -> Dict:
    """Follow ``len(batches)`` steps from the seed's weights. Returns
    ``{"loss": [each of the first compared steps], "grad1": {leaf: norm},
    "change": {leaf: norm after the first compared steps}, "tables": {leaf:
    norm of each table's change after the last step}}``, with leaves named
    ``emb/<field>`` and ``dense/<path>``."""
    from bench import weights
    fs = fields(cfg)
    rows, stream, field_of, cols = [], [], [], []
    start = 0
    for k, (name, vocab, _) in enumerate(fs):
        hashed = np.stack([hashed_rows(name, vocab, b["fields"][name]["ids"][:, 0])
                           for b in batches])                       # [S, B]
        uniq = np.unique(hashed)
        rows.append(uniq)
        stream.append(np.full(uniq.shape, EMB_STREAM0 + k))
        field_of.append(np.full(uniq.shape, k))
        cols.append(start + np.searchsorted(uniq, hashed))
        start += uniq.shape[0]
    idx = np.stack(cols, axis=-1).astype(np.int32)                   # [S, B, F]
    dense_x = (np.stack([b["dense"] for b in batches]) if cfg.get("n_dense", 0) else None)
    labels = np.stack([b["labels"] for b in batches])
    with jax.default_matmul_precision("highest"):
        losses, grad1, change, tables = _program(cfg, dtype, compared)(
            weights.seed_words(seed), np.concatenate(rows).astype(np.uint32),
            np.concatenate(stream).astype(np.uint32),
            np.concatenate(field_of).astype(np.int32), idx, dense_x, labels)
    return {"loss": [float(x) for x in losses],
            "grad1": {k: float(v) for k, v in grad1.items()},
            "change": {k: float(v) for k, v in change.items()},
            "tables": {k: float(v) for k, v in tables.items()}}
