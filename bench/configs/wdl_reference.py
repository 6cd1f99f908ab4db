"""Plain reference of the wide-and-deep models these configurations run:
DeepFM (FM + linear + MLP over the field embeddings) and DCN-v2 (stacked
full-rank or low-rank cross network, then the MLP), trained with Adam on the
dense parameters and row-wise Adagrad on the embedding rows.

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
* Configuration keys beyond the sizes: ``multi_hot``, the ids a sample of
  each categorical field in field order (1 each where absent; a numeric
  bucket field has 1); ``bottom_mlp``, the widths of an MLP over the
  ``n_dense`` numeric features with ReLU after every layer (torchrec's
  ``DenseArch``); and a ``cross`` interaction's ``low_rank: r``.
* Model. ``x_f`` is the sum of the rows of field f's ids (its bag), ``X``
  the ``[B, F, d]`` stack in configuration order, ``base`` its ``[B, F*d]``
  flattening followed by the numeric features, or by the bottom MLP's
  output where there is one. This is the program's order; torchrec's DLRM
  puts the dense part first, a permutation of the same equations. ``fm``
  adds ``0.5 * sum_d((sum_f x)^2 - sum_f x^2)`` to the logit, ``linear``
  adds ``sum_f x_f @ W_f``; ``cross`` replaces the MLP input with
  ``x_{l+1} = base * (x_l @ W_l + b_l) + x_l`` (``x_0 = base``), or with
  ``low_rank`` ``x_{l+1} = base * ((x_l @ W_down_l) @ W_up_l + b_l) + x_l``.
  The MLP has ReLU between layers and one output. The loss is the mean
  binary cross-entropy with logits over the global batch.
* Updates. Adam (b1 0.9, b2 0.999, eps 1e-8) on every dense leaf; on each
  table the gradients of a step's duplicate rows are summed and a touched
  row takes ``acc += mean_d(g^2)``, ``w -= lr * g / sqrt(acc + eps)``.

``readings`` follows the first steps of a run and returns what the
correctness check compares: the loss of each of the first three steps, each
leaf's norm of the first gradient and of its change after the third step,
and each table's norm of its change after the last step followed. It holds
one table of every row the run touches; each step gathers the rows its
batch touches, differentiates with respect to them alone and scatters them
back, so a step's temporaries follow its own distinct rows.
"""
from __future__ import annotations

import json
import math
import zlib
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_KNUTH = 2654435761
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
ADAGRAD_EPS = 1e-8
EMB_STREAM0, DENSE_STREAM0 = 0, 1_000_000


# ------------------------------------------------------------------ model
class Field(NamedTuple):
    name: str
    vocab: int
    dim: int
    hot: int     # ids a sample, sum-pooled


def fields(cfg: Dict) -> List[Field]:
    """Every field, in configuration order."""
    d = cfg["embed_dim"]
    vocab = cfg["categorical_vocab"]
    hot = cfg.get("multi_hot", [1] * len(vocab))
    if len(hot) != len(vocab) or min(hot) < 1:
        raise ValueError(f"multi_hot names {len(hot)} bags of at least one id, "
                         f"one for each of the {len(vocab)} categorical fields")
    out = [Field(f"cat_{i}", int(v), d, int(k)) for i, (v, k) in enumerate(zip(vocab, hot))]
    out += [Field(f"numb_{i}", int(cfg["numeric_buckets"]), d, 1)
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
    n_dense, bottom = cfg.get("n_dense", 0), list(cfg.get("bottom_mlp", []))
    spec = _mlp_spec("bottom", [n_dense] + bottom)
    base = sum(f.dim for f in fs) + (bottom[-1] if bottom else n_dense)
    for n, it in enumerate(cfg["interactions"]):
        kind = it["kind"]
        if kind == "linear":
            spec += [((f"i{n}_linear", f.name), (f.dim, 1), 0.01) for f in fs]
        elif kind == "cross":
            r = it.get("low_rank")
            for l in range(it["n_layers"]):
                at = (f"i{n}_cross", f"l{l}")
                spec += ([(at + ("w_down",), (base, r), 1 / math.sqrt(base)),
                          (at + ("w_up",), (r, base), 1 / math.sqrt(r))] if r else
                         [(at + ("w",), (base, base), 1 / math.sqrt(base))])
                spec.append((at + ("b",), (base,), 0.0))
        elif kind != "fm":
            raise ValueError(f"the reference has no interaction {kind!r}")
    return spec + _mlp_spec("top", [base] + list(cfg["mlp_dims"]) + [1])


def _mlp_spec(name: str, dims: List[int]):
    spec = []
    for l in range(len(dims) - 1):
        spec += [((name, f"l{l}", "w"), (dims[l], dims[l + 1]), math.sqrt(2.0 / dims[l])),
                 ((name, f"l{l}", "b"), (dims[l + 1],), 0.0)]
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


def _mlp(p: Dict, x: jnp.ndarray, relu_last: bool) -> jnp.ndarray:
    for l in range(len(p)):
        x = x @ p[f"l{l}"]["w"] + p[f"l{l}"]["b"]
        if relu_last or l < len(p) - 1:
            x = jax.nn.relu(x)
    return x


def pool(cfg: Dict, rows: jnp.ndarray) -> jnp.ndarray:
    """``rows``: ``[B, T, d]``, a sample's ids field after field, each
    field's bag in turn. Returns ``[B, F, d]``, each bag's rows summed."""
    hot = [f.hot for f in fields(cfg)]
    seg = np.repeat(np.arange(len(hot)), hot)
    return jax.ops.segment_sum(jnp.swapaxes(rows, 0, 1), seg,
                               num_segments=len(hot)).swapaxes(0, 1)


def logits(cfg: Dict, params: Dict, x: jnp.ndarray, dense_x) -> jnp.ndarray:
    """``x``: ``[B, F, d]`` pooled field rows; ``dense_x``: ``[B, n_dense]``
    or None."""
    b = x.shape[0]
    base = x.reshape(b, -1)
    if dense_x is not None:
        dense_x = dense_x.astype(x.dtype)
        if "bottom" in params:
            dense_x = _mlp(params["bottom"], dense_x, relu_last=True)
        base = jnp.concatenate([base, dense_x], axis=-1)
    wide = jnp.zeros((b, 1), x.dtype)
    deep = base
    for n, it in enumerate(cfg["interactions"]):
        if it["kind"] == "fm":
            s = x.sum(axis=1)
            wide = wide + 0.5 * (s * s - (x * x).sum(axis=1)).sum(-1, keepdims=True)
        elif it["kind"] == "linear":
            p = params[f"i{n}_linear"]
            w = jnp.stack([p[f.name] for f in fields(cfg)])  # [F, d, 1]
            wide = wide + jnp.einsum("bfd,fdo->bo", x, w)
        elif it["kind"] == "cross":
            p = params[f"i{n}_cross"]
            h = base
            for l in range(it["n_layers"]):
                q = p[f"l{l}"]
                z = (h @ q["w_down"]) @ q["w_up"] if "w_down" in q else h @ q["w"]
                h = base * (z + q["b"]) + h
            deep = h
    return _mlp(params["top"], deep, relu_last=False) + wide


def mean_loss(cfg, params, table, idx, dense_x, labels):
    """``table``: the rows the batch touches, ``[U, d]``; ``idx``: ``[B,
    T]`` rows of it, each sample's bags one after another."""
    z = logits(cfg, params, pool(cfg, table[idx]), dense_x).astype(jnp.float32)[:, 0]
    y = labels.astype(jnp.float32)
    ls = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    return ls.sum() / z.shape[0]


# ------------------------------------------------------------ one step
def _step(cfg, params, opt, table, acc, at, idx, dense_x, labels):
    """One step on the rows ``at`` of ``table`` that the batch touches, each
    once (padded with ``len(table)``, which reads a row no sample uses and
    writes nothing); ``idx``: ``[B, T]`` positions in ``at``. The gradient
    is taken with respect to those rows alone, so a step's temporaries
    follow its own distinct rows."""
    rows = jnp.take(table, at, axis=0, mode="clip")
    loss, (g_p, g_t) = jax.value_and_grad(mean_loss, argnums=(1, 2))(
        cfg, params, rows, idx, dense_x, labels)
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, opt["m"], g_p)
    v = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, opt["v"], g_p)
    c1 = 1 - ADAM_B1 ** t.astype(jnp.float32)
    c2 = 1 - ADAM_B2 ** t.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, m, v: (p - cfg["lr_dense"] * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS)
                         ).astype(p.dtype), params, m, v)
    # the gradient of a row is the sum over its duplicates in the step; an
    # untouched row keeps its value and accumulator
    a = jnp.take(acc, at, mode="clip") + jnp.mean(g_t * g_t, axis=-1)
    rows = (rows - cfg["lr_emb"] * g_t / jnp.sqrt(a + ADAGRAD_EPS)[:, None]
            ).astype(table.dtype)
    table = table.at[at].set(rows, mode="drop")
    acc = acc.at[at].set(a, mode="drop")
    return loss, g_p, g_t, params, {"m": m, "v": v, "t": t}, table, acc


def _norms(tree) -> Dict[str, jnp.ndarray]:
    return {"/".join(str(getattr(p, "key", p)) for p in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


BLOCK = 1 << 18  # rows whose initial values are made at a time
_PROGRAMS: Dict[Tuple, object] = {}


def _program(cfg: Dict, dtype, compared: int):
    """One jitted program that follows the steps from the seed: it makes
    the touched rows and the dense leaves, runs the first ``compared`` steps
    one by one and the rest in a loop, and returns the losses and norms. Its
    inputs carry the seed, so it compiles once for a traffic's row counts.
    Besides the table and its accumulator, nothing it holds grows with the
    rows the run touches: a table's change is taken against its initial
    rows made again from the seed, ``BLOCK`` rows at a time."""
    key = (json.dumps(cfg, sort_keys=True), jnp.dtype(dtype).name, compared)
    if key in _PROGRAMS:
        return _PROGRAMS[key]
    from bench import weights
    fs = fields(cfg)
    names = [f.name for f in fs]
    d = fs[0].dim
    if any(f.dim != d for f in fs):
        raise ValueError("the reference keeps one table of rows for fields of one width")

    def by_field(sq, field_of):
        return jax.ops.segment_sum(sq, field_of, num_segments=len(fs))

    def named(sq):
        return {f"emb/{n}": v for n, v in zip(names, jnp.sqrt(sq))}

    def run(seed, rows, stream, field_of, at, idx, dense_x, labels):
        n = rows.shape[0]
        table = weights.table_rows(seed, stream, rows, d, emb_std(d)).astype(dtype)
        params0 = init_dense(cfg, seed)
        params = jax.tree.map(lambda p: p.astype(dtype), params0)
        opt = {"m": jax.tree.map(jnp.zeros_like, params),
               "v": jax.tree.map(jnp.zeros_like, params),
               "t": jnp.zeros((), jnp.int32)}
        acc = jnp.zeros((n,), dtype)

        def dx(s):
            return None if dense_x is None else dense_x[s].astype(dtype)

        def table_change(table):
            blk = min(BLOCK, n)

            def body(i, sq):
                start = jnp.minimum(i * blk, n - blk)     # the last block may overlap
                part = start + jnp.arange(blk, dtype=jnp.int32)
                w0 = weights.table_rows(seed, lax.dynamic_slice_in_dim(stream, start, blk),
                                        lax.dynamic_slice_in_dim(rows, start, blk),
                                        d, emb_std(d))
                w = lax.dynamic_slice_in_dim(table, start, blk).astype(jnp.float32)
                d2 = jnp.where(part >= i * blk, jnp.sum(jnp.square(w - w0), -1), 0.0)
                return sq + by_field(d2, lax.dynamic_slice_in_dim(field_of, start, blk))
            return named(lax.fori_loop(0, -(-n // blk), body,
                                       jnp.zeros((len(fs),), jnp.float32)))

        def change():
            delta = jax.tree.map(lambda p, p0: p.astype(jnp.float32) - p0, params, params0)
            return {**table_change(table),
                    **{f"dense/{k}": v for k, v in _norms(delta).items()}}

        losses, grad1 = [], None
        for s in range(compared):
            loss, g_p, g_t, params, opt, table, acc = _step(
                cfg, params, opt, table, acc, at[s], idx[s], dx(s), labels[s])
            losses.append(loss)
            if s == 0:
                sq = jnp.sum(jnp.square(g_t.astype(jnp.float32)), -1)
                grad1 = {**named(by_field(sq, jnp.take(field_of, at[0], mode="clip"))),
                         **{f"dense/{k}": v for k, v in _norms(g_p).items()}}
        change_c = change()

        def later(carry, s):
            params, opt, table, acc = carry
            _, _, _, params, opt, table, acc = _step(
                cfg, params, opt, table, acc, at[s], idx[s], dx(s), labels[s])
            return (params, opt, table, acc), None

        (params, opt, table, acc), _ = lax.scan(
            later, (params, opt, table, acc), jnp.arange(compared, labels.shape[0]))
        return jnp.stack(losses), grad1, change_c, table_change(table)

    _PROGRAMS[key] = jax.jit(run)
    return _PROGRAMS[key]


def inputs(cfg: Dict, seed: int, batches: Sequence[Dict]) -> Tuple:
    """The arguments of ``_program`` for ``batches``: the seed's words; the
    rows any batch touches, every field's after the one before, with their
    weight streams and fields; each step's distinct rows among them,
    ``[S, U]``, padded to the largest step's count ``U`` with one past the
    last row; each sample's ids as positions in its step's rows, ``[S, B,
    T]``; the numeric features (or None); and the labels."""
    from bench import weights
    fs = fields(cfg)
    rows, stream, field_of, cols = [], [], [], []
    start = 0
    for k, f in enumerate(fs):
        hashed = np.stack([hashed_rows(f.name, f.vocab, b["fields"][f.name]["ids"])
                           for b in batches])                       # [S, B, hot]
        uniq = np.unique(hashed)
        rows.append(uniq)
        stream.append(np.full(uniq.shape, EMB_STREAM0 + k))
        field_of.append(np.full(uniq.shape, k))
        cols.append(start + np.searchsorted(uniq, hashed))
        start += uniq.shape[0]
    run_idx = np.concatenate(cols, axis=-1)                         # [S, B, T]
    steps = [np.unique(x, return_inverse=True) for x in run_idx]
    at = np.full((len(steps), max(u.size for u, _ in steps)), start, np.int32)
    for s, (u, _) in enumerate(steps):
        at[s, :u.size] = u
    idx = np.stack([inv.reshape(run_idx.shape[1:]) for _, inv in steps]).astype(np.int32)
    dense_x = (np.stack([b["dense"] for b in batches]) if cfg.get("n_dense", 0) else None)
    labels = np.stack([b["labels"] for b in batches])
    return (weights.seed_words(seed), np.concatenate(rows).astype(np.uint32),
            np.concatenate(stream).astype(np.uint32),
            np.concatenate(field_of).astype(np.int32), at, idx, dense_x, labels)


def readings(cfg: Dict, seed: int, batches: Sequence[Dict], dtype=jnp.float32,
             compared: int = 3, precision: str = "highest") -> Dict:
    """Follow ``len(batches)`` steps from the seed's weights, in ``dtype``
    with matmuls at ``precision`` (``jax.default_matmul_precision``).
    Returns ``{"loss": [each of the first compared steps], "grad1": {leaf:
    norm}, "change": {leaf: norm after the first compared steps}, "tables":
    {leaf: norm of each table's change after the last step}}``, with leaves
    named ``emb/<field>`` and ``dense/<path>``."""
    with jax.default_matmul_precision(precision):
        losses, grad1, change, tables = _program(cfg, dtype, compared)(
            *inputs(cfg, seed, batches))
    return {"loss": [float(x) for x in losses],
            "grad1": {k: float(v) for k, v in grad1.items()},
            "change": {k: float(v) for k, v in change.items()},
            "tables": {k: float(v) for k, v in tables.items()}}
