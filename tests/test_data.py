"""Data layer: zipf skew (paper Fig. 3), pipeline stragglers, graph sampler."""
import time

import numpy as np
import pytest

from repro.configs import get_config
from repro.data.graph import molecule_batch, pad_subgraph, sample_neighbors, synthetic_graph
from repro.data.pipeline import BatchProducerError, Prefetcher, ReplayableStream
from repro.data.synthetic import make_batch, zipf_ids


def test_zipf_head_mass():
    """Paper §II-B: top 20% of ids must cover the majority of queries."""
    rng = np.random.default_rng(0)
    ids = zipf_ids(rng, 10_000, 200_000, a=1.2)
    counts = np.bincount(ids, minlength=10_000)
    top20 = np.sort(counts)[::-1][:2000].sum() / counts.sum()
    assert top20 > 0.5


def test_make_batch_shapes():
    cfg = get_config("sasrec", smoke=True)
    b = make_batch(cfg, 16)
    for f in cfg.fields:
        assert b["fields"][f.name]["ids"].shape == (16, f.max_len)
        w = b["fields"][f.name]["weights"]
        assert w.shape == (16, f.max_len)
        if f.max_len > 1 and f.name != "pos":
            assert (w.sum(1) >= 1).all()  # at least one valid position
        assert (b["fields"][f.name]["ids"] < f.vocab).all()
    assert b["labels"].shape == (16,)


def test_prefetcher_backup_on_straggle():
    def gen():
        yield 1
        yield 2
        time.sleep(10)  # straggler
        yield 3

    pf = Prefetcher(gen(), depth=2, timeout_s=0.3)
    assert next(pf) == 1
    assert next(pf) == 2
    got = next(pf)  # generator is stuck -> backup batch served
    assert got == 2
    assert pf.stats["backup_served"] == 1
    pf.close()


def test_prefetcher_close_with_full_queue_reaps_worker():
    """A worker blocked on a full queue must observe close() and exit — the
    old blocking q.put() would hang the thread forever after close()."""
    def gen():
        i = 0
        while True:  # endless producer: guaranteed to fill the queue
            yield i
            i += 1

    pf = Prefetcher(gen(), depth=2, timeout_s=0.5)
    assert next(pf) == 0
    deadline = time.monotonic() + 2.0  # let the worker block in put()
    while pf.q.full() is False and time.monotonic() < deadline:
        time.sleep(0.01)
    pf.close()
    assert not pf._thread.is_alive()
    pf.close()  # idempotent


def test_prefetcher_close_unblocks_immediately_when_idle():
    def gen():
        yield from range(3)

    pf = Prefetcher(gen(), depth=8, timeout_s=0.5)
    assert [next(pf) for _ in range(3)] == [0, 1, 2]
    pf.close()
    assert not pf._thread.is_alive()


def test_prefetcher_producer_death_raises():
    """A producer that raises must surface its error, never end the stream
    as if it were exhausted (a trainer would report a short run as done)."""
    def gen():
        yield 0
        raise OSError("disk gone")

    pf = Prefetcher(gen(), depth=2, timeout_s=5.0)
    assert next(pf) == 0
    t0 = time.monotonic()
    with pytest.raises(BatchProducerError) as ei:
        next(pf)
    assert isinstance(ei.value.__cause__, OSError)
    assert time.monotonic() - t0 < 2.0  # seen at once, not after the timeout
    pf.close()


def test_prefetcher_timeout_without_backup_raises():
    def gen():
        time.sleep(10)
        yield 0

    pf = Prefetcher(gen(), depth=2, timeout_s=0.2)
    with pytest.raises(TimeoutError):
        next(pf)
    assert pf.stats["backup_served"] == 0
    pf.close()


def test_replayable_stream_stats_survive_reopen():
    rs = ReplayableStream(lambda start: Prefetcher(iter(range(start, start + 3)),
                                                   depth=2))
    assert [next(rs) for _ in range(2)] == [0, 1]
    rs.seek(0)
    assert next(rs) == 0
    deadline = time.monotonic() + 2.0
    while rs.stats["produced"] < 5 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert rs.stats == {"produced": 5, "backup_served": 0}
    rs.close()


def test_neighbor_sampler_valid():
    g = synthetic_graph(500, 4000, d_feat=8, seed=1)
    rng = np.random.default_rng(0)
    seeds = rng.integers(0, 500, 32)
    sub = sample_neighbors(g, seeds, (5, 3), rng)
    n = len(sub["node_ids"])
    assert sub["src"].max() < n and sub["dst"].max() < n
    # sampled edges correspond to real graph edges
    gid = sub["node_ids"]
    real = set(zip(g["src"].tolist(), g["dst"].tolist()))
    for s, d in zip(sub["src"][:50], sub["dst"][:50]):
        assert (gid[d], gid[s]) in real  # message dst<-src == edge dst->nbr
    padded = pad_subgraph(sub, g, max_nodes=n + 16, max_edges=len(sub["src"]) + 8)
    assert padded["nodes"].shape[0] == n + 16
    assert padded["edge_w"].sum() == len(sub["src"])


def test_molecule_batch_offsets():
    b = molecule_batch(4, 6, 10)
    assert b["src"].max() < 24 and b["graph_ids"].shape == (24,)
    # edges stay within their own molecule
    assert (b["src"] // 6 == b["dst"] // 6).all()
