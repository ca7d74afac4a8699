"""The fused walk's gather route: chains of structured hops walk their
composed row map on the device, whatever their dense planes would take.

Pins (a) that the route answers byte for byte as the per-op walkers
(``forward_``/``backward_record_masks_batch``) over joins probed on either
side, left and outer joins, dangling rows, duplicate keys, filter -> join
-> filter chains, appends, identity-only chains and randomized pipelines,
with empty probes and B = 1 and 64, the route forced by a small
``max_plane_bytes``; (b) the kernel (:func:`repro.kernels.ops.lineage_gather`'s,
launched as ``gather_walk``), Pallas in interpret mode, against its oracle
:func:`repro.kernels.ref.lineage_gather_ref`, with a key heavy enough
that its segment spans several grid steps; (c) one resident operand
per chain, serving both directions, with the plane store's counters; and
(d) the route reached through ``prov(...)`` and a session at a size over
the default cap, with its counters and spans; and (e) the word scan that
finds the probe stack's set entries, and the per-probe answers the route
gives without a dense stack (checked beside every stack it answers); and
(f) the route's choice under the default cap: structured chains take the
row map, unstructured ones and structured ones whose answer table is too
large the dense planes, and the Table VIII COMPAS chain the row map.
"""
from collections import defaultdict

import jax.numpy as jnp
import numpy as np
import pytest

import pipegen
from repro.core import query as Q
from repro.core.compose import path_tensors
from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.table import Table
from repro.dataprep.tracked import track
from repro.dataprep.usecases import USECASES
from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.provenance import QuerySession, prov


def _table(rng, n, key, keys):
    return Table.from_columns({
        key: keys.astype(np.float32),
        f"v{n}": rng.normal(size=n).astype(np.float32),
    })


def _join(how="inner", dup=False, filters=False, append=False, seed=0,
          n_dim=90, n_fact=70):
    """dim [-> filter] [-> append] ; fact.join(dim) [-> filter] -> sink.
    ``dup`` draws fact keys with replacement (many joined rows per dim
    row); keys absent from dim leave fact rows dangling (left, outer), and
    dim rows no fact names dangle (outer)."""
    rng = np.random.default_rng(seed)
    idx = ProvenanceIndex(f"gather-{how}-{dup}-{filters}-{append}-{seed}")
    dim = track(_table(rng, n_dim, "k", np.arange(n_dim)), idx, "dim")
    if filters:
        dim = dim.filter_rows(rng.random(n_dim) > 0.2)
    if append:
        extra = _table(rng, 12, "k", np.arange(n_dim, n_dim + 12))
        dim = dim.append(track(extra, idx, "extra"))
    keys = rng.integers(0, n_dim + 20, size=n_fact) if dup else \
        rng.choice(n_dim + 20, size=n_fact, replace=False)
    fact = track(_table(rng, n_fact, "k", keys), idx, "fact")
    out = fact.join(dim, on="k", how=how)
    if filters:
        out = out.filter_rows(rng.random(out.table.n_rows) > 0.25)
    out.mark_sink()
    return idx, out.dataset_id


def _identity_chain(seed=0):
    rng = np.random.default_rng(seed)
    idx = ProvenanceIndex(f"gather-identity-{seed}")
    t = track(Table.from_columns({
        "x": rng.normal(size=80).astype(np.float32),
        "g": rng.integers(0, 4, 80).astype(np.float32),
    }), idx, "dim")
    sink = t.value_transform("x", "scale", factor=2.0).normalize(["x"]) \
        .onehot("g", n_values=4)
    sink.mark_sink()
    return idx, sink.dataset_id


CASES = {
    "inner": lambda: _join("inner"),
    "left": lambda: _join("left"),
    "outer": lambda: _join("outer"),
    "dup_keys": lambda: _join("inner", dup=True),
    "dup_keys_outer": lambda: _join("outer", dup=True),
    "filter_join_filter": lambda: _join("left", dup=True, filters=True),
    "append_join": lambda: _join("outer", append=True),
    "identity_only": _identity_chain,
}


def _probes(rng, batch, n):
    rows = rng.random((batch, n)) < 0.15
    rows[0] = False                     # an empty probe
    if batch > 1:
        rows[1] = True                  # every row
    return rows


def _walker(idx, src, dst, rows, direction):
    walk = Q.forward_record_masks_batch if direction == "fwd" else \
        Q.backward_record_masks_batch
    return walk(idx, src, rows).get(
        dst, np.zeros((rows.shape[0], idx.datasets[dst].n_rows), dtype=bool))


def _routed(idx, src, dst, rows, direction, **kw):
    """The route's stack, checked against its per-probe answer."""
    got = {}
    for per_probe in (False, True):
        counters = defaultdict(int)
        got[per_probe] = Q.fused_walk_record_masks_batch(
            idx, src, dst, rows, direction, max_plane_bytes=0,
            counters=counters, per_probe=per_probe, **kw)
        assert got[per_probe] is not None, \
            "the route refused a structured chain"
        assert counters["gather_walk"] == 1
        assert counters["fused_walk_oversize"] == 0
    stack, per = got[False], got[True]
    assert len(per) == len(stack)
    for answer, row in zip(per, stack):
        np.testing.assert_array_equal(answer, np.flatnonzero(row))
        assert answer.dtype == np.flatnonzero(row).dtype
    return stack


# each case probed from its dimension side and, where it joins, its fact side
SIDES = [(case, side) for case in sorted(CASES) for side in ("dim", "fact")
         if side == "dim" or case != "identity_only"]


@pytest.mark.parametrize("batch", [1, 64])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("case,side", SIDES)
def test_gather_route_matches_the_walkers(case, side, direction, batch):
    idx, sink = CASES[case]()
    rng = np.random.default_rng(batch)
    src, dst = (side, sink) if direction == "fwd" else (sink, side)
    rows = _probes(rng, batch, idx.datasets[src].n_rows)
    got = _routed(idx, src, dst, rows, direction)
    np.testing.assert_array_equal(got, _walker(idx, src, dst, rows, direction))


@pytest.mark.parametrize("seed", range(12))
def test_gather_route_matches_the_walkers_on_random_pipelines(seed):
    idx, sink, rng = pipegen.random_pipeline(seed)
    store = Q.WalkPlaneStore(defaultdict(int))
    for src in idx.datasets:
        if src == sink or idx.datasets[src].n_rows == 0:
            continue
        for direction, (a, b) in (("fwd", (src, sink)), ("bwd", (sink, src))):
            rows = _probes(rng, 5, idx.datasets[a].n_rows)
            got = _routed(idx, a, b, rows, direction, planes=store)
            np.testing.assert_array_equal(got, _walker(idx, a, b, rows,
                                                       direction))


def _heavy_join(seed=0):
    """A many-to-one join whose key 7 has 2,000 of the 2,600 fact rows: its
    forward segment spans 16 chunks of 128 lanes, two grid steps."""
    rng = np.random.default_rng(seed)
    idx = ProvenanceIndex(f"gather-heavy-{seed}")
    dim = track(_table(rng, 50, "k", np.arange(50)), idx, "dim")
    keys = rng.integers(0, 60, size=2_600)
    keys[rng.permutation(2_600)[:2_000]] = 7
    fact = track(_table(rng, 2_600, "k", keys), idx, "fact")
    out = fact.join(dim, on="k", how="left").mark_sink()
    return idx, out.dataset_id


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("heavy", [False, True])
def test_gather_route_on_the_interpreted_kernel(direction, heavy):
    idx, sink = _heavy_join() if heavy else \
        _join("outer", dup=True, filters=True, seed=3)
    src, dst = ("dim", sink) if direction == "fwd" else (sink, "dim")
    rows = _probes(np.random.default_rng(4), 8, idx.datasets[src].n_rows)
    got = _routed(idx, src, dst, rows, direction, use_pallas=True)
    np.testing.assert_array_equal(got, _walker(idx, src, dst, rows, direction))


def test_unstructured_chains_over_the_cap_stay_on_the_host():
    idx, sink = _join("inner")
    op = idx.ops[idx.producer[sink]]
    op.tensor = op.tensor.as_coo()      # the same relation, no structure
    counters = defaultdict(int)
    rows = _probes(np.random.default_rng(0), 3, idx.datasets["dim"].n_rows)
    assert Q.fused_walk_record_masks_batch(
        idx, "dim", sink, rows, "fwd", max_plane_bytes=0,
        counters=counters) is None
    assert counters["fused_walk_oversize"] == 1
    assert counters["gather_walk"] == 0


# -- the route's choice under the default cap ---------------------------------
def _wide(seed=0):
    """filter -> left join on repeated keys -> filter, over 1,500 dimension
    and 1,400 fact rows: 3 x 1,500^2 bits of planes (844 KB), under the
    default cap and over a sparse plan's answer table (64 KiB)."""
    return _join("left", dup=True, filters=True, seed=seed, n_dim=1500,
                 n_fact=1400)


def _sparse(rng, batch, n):
    rows = rng.random((batch, n)) < 0.005
    rows[0] = False                     # an empty probe
    return rows


def _plane_bytes(idx, src, dst, direction):
    up, down = (src, dst) if direction == "fwd" else (dst, src)
    chain = path_tensors(idx, up, down)
    n = max(max(op.tensor.n_in[s], op.tensor.n_out) for op, s in chain)
    return len(chain) * n * n // 8


def _dense_launches():
    return K.launch_counts().get("batched_walk", 0)


@pytest.mark.parametrize("per_probe", [False, True])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_structured_chains_under_the_cap_take_the_gather_route(direction,
                                                               per_probe):
    idx, sink = _wide()
    src, dst = ("dim", sink) if direction == "fwd" else (sink, "dim")
    assert 64 << 10 < _plane_bytes(idx, src, dst, direction) <= 256 << 20
    rows = _sparse(np.random.default_rng(6), 8, idx.datasets[src].n_rows)
    counters = defaultdict(int)
    before = _dense_launches()
    got = Q.fused_walk_record_masks_batch(idx, src, dst, rows, direction,
                                          counters=counters,
                                          per_probe=per_probe)
    assert _dense_launches() == before
    assert counters["gather_walk"] == 1
    assert counters["gather_walk_declined"] == 0
    assert counters["fused_walk_oversize"] == 0
    want = _walker(idx, src, dst, rows, direction)
    if per_probe:
        assert len(got) == len(want)
        for answer, row in zip(got, want):
            np.testing.assert_array_equal(answer, np.flatnonzero(row))
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_unstructured_chains_under_the_cap_launch_the_dense_walk(direction):
    idx, sink = _wide()
    op = idx.ops[idx.producer[sink]]
    op.tensor = op.tensor.as_coo()      # the same relation, no structure
    src, dst = ("dim", sink) if direction == "fwd" else (sink, "dim")
    rows = _sparse(np.random.default_rng(7), 8, idx.datasets[src].n_rows)
    counters = defaultdict(int)
    before = _dense_launches()
    got = Q.fused_walk_record_masks_batch(idx, src, dst, rows, direction,
                                          counters=counters, per_probe=True)
    assert _dense_launches() == before + 1
    for k in ("gather_walk", "gather_walk_declined", "fused_walk_oversize"):
        assert counters[k] == 0, k
    np.testing.assert_array_equal(got, _walker(idx, src, dst, rows,
                                               direction))


# structured chains whose answer table the route declines: (a) over a
# 90-row join's 1 KB of planes, whatever the plan; (b) key 7's 2,000 rows
# widen the forward table to 2,048 lanes (1 MiB for 128 probe rows), over
# the join's 845 KB of planes; (c) over a table cap patched to 1 KiB
DECLINED = [("small_planes", "fwd"), ("small_planes", "bwd"),
            ("heavy_key", "fwd"), ("table_cap", "fwd"), ("table_cap", "bwd")]


@pytest.mark.parametrize("case,direction", DECLINED)
def test_declined_structured_chains_go_dense_under_the_cap(case, direction,
                                                           monkeypatch):
    if case == "table_cap":
        monkeypatch.setattr(K, "GATHER_TABLE_BYTES", 1024)
    idx, sink = {"small_planes": lambda: _join("outer", dup=True),
                 "heavy_key": _heavy_join, "table_cap": _wide}[case]()
    src, dst = ("dim", sink) if direction == "fwd" else (sink, "dim")
    rows = _probes(np.random.default_rng(8), 4, idx.datasets[src].n_rows)
    counters = defaultdict(int)
    before = _dense_launches()
    got = Q.fused_walk_record_masks_batch(idx, src, dst, rows, direction,
                                          counters=counters)
    assert _dense_launches() == before + 1
    assert counters["gather_walk_declined"] == 1
    assert counters["gather_walk"] == counters["fused_walk_oversize"] == 0
    want = _walker(idx, src, dst, rows, direction)
    np.testing.assert_array_equal(got, want)
    # over the cap only the route's own table cap binds; past it, with no
    # dense route left, the host walker answers
    counters = defaultdict(int)
    got = Q.fused_walk_record_masks_batch(idx, src, dst, rows, direction,
                                          max_plane_bytes=0,
                                          counters=counters)
    assert counters["gather_walk_declined"] == 0
    if case == "table_cap":
        assert got is None
        assert counters["fused_walk_oversize"] == 1
        assert counters["gather_walk"] == 0
    else:
        np.testing.assert_array_equal(got, want)
        assert counters["gather_walk"] == 1
        assert counters["fused_walk_oversize"] == 0


def test_compas_record_plans_take_the_gather_route():
    """The Table VIII COMPAS chain (7 structured ops, 7 x 7,214^2 bits of
    planes under the cap): every record plan of a session with the fused
    walk on, both ways, answers from the row map as the host walker."""
    make, run = USECASES["compas"]
    idx = ProvenanceIndex("compas-route")
    sink = run(idx, make()).dataset_id
    src = "compas_src"
    assert _plane_bytes(idx, src, sink, "fwd") <= 256 << 20
    sess = QuerySession(idx, use_hopcache=False, fused_walk=True)
    host = QuerySession(idx, use_hopcache=False, fused_walk=False)
    rng = np.random.default_rng(9)
    before = _dense_launches()
    n_plans = 0
    for batch, size in ((64, 4), (8, 1)):
        for direction, a, b in (("fwd", src, sink), ("bwd", sink, src)):
            n = idx.datasets[a].n_rows
            rows = [np.sort(rng.choice(n, size=size, replace=False))
                    for _ in range(batch)]
            q = prov(idx).source(a).rows_batch(rows)
            q = q.forward() if direction == "fwd" else q.backward()
            plan = q.to(b).plan()
            got, want = sess.run(plan), host.run(plan)
            assert len(got) == len(want) == batch
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w)
                assert g.dtype == w.dtype
            n_plans += 1
    c = sess.counters
    assert c["plans"] == c["fused_walk"] == c["gather_walk"] == n_plans
    assert c["gather_walk_declined"] == c["fused_walk_oversize"] == 0
    assert _dense_launches() == before
    # one resident row map answers both directions
    assert (c["walk_plane_misses"], c["walk_plane_hits"]) == (1, n_plans - 1)


# -- the kernel against its oracle -------------------------------------------
def _map(seed, n_src, n_dst, fanout):
    """A random map; ``fanout`` source rows take 60% of its links."""
    rng = np.random.default_rng(seed)
    g = rng.integers(-1, n_src, size=n_dst)
    if fanout:
        heavy = rng.random(n_dst) < 0.6
        g[heavy] = rng.integers(0, fanout, size=int(heavy.sum()))
    return g


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("seed,n_src,n_dst,fanout,q", [
    (0, 300, 280, 0, 1),
    (1, 1000, 900, 0, 300),           # Q not a multiple of the block
    (2, 40, 700, 3, 64),              # segments of several 128-lane chunks
    (3, 500, 0, 0, 16),               # an empty map
    (4, 30, 4000, 1, 16),             # one heavy key: ~19 chunks, 19 steps
    (5, 30, 5200, 2, 16),             # two heavy keys over 2 x 8-chunk steps
])
def test_gather_walk_kernel_matches_its_oracle(direction, seed, n_src, n_dst,
                                               fanout, q):
    g = _map(seed, n_src, n_dst, fanout)
    rng = np.random.default_rng(seed + 100)
    n_probe = n_src if direction == "fwd" else max(n_dst, 1)
    queries = rng.integers(-1, n_probe, size=q).astype(np.int32)
    if direction == "bwd" and n_dst == 0:
        queries[:] = -1
    gmap = K.prepare_gather_walk(g, n_src, use_pallas=True, interpret=True)
    got = np.asarray(K.gather_walk(queries, gmap, direction))
    ptr, col_p, deg = gmap.fwd if direction == "fwd" else gmap.bwd
    want = np.asarray(R.lineage_gather_ref(jnp.asarray(queries), ptr,
                                           col_p[0], max_deg=deg))
    np.testing.assert_array_equal(got, want)
    for row, r in zip(got, queries):   # and against the map itself
        if r < 0:
            expect = np.zeros(0, dtype=np.int64)
        elif direction == "fwd":
            expect = np.flatnonzero(g == r)
        else:
            expect = g[r:r + 1][g[r:r + 1] >= 0]
        np.testing.assert_array_equal(row[row >= 0], expect)


def test_gather_walk_counts_its_launches():
    gmap = K.prepare_gather_walk(np.array([2, -1, 0]), 3)
    before = K.launch_counts().get("gather_walk", 0)
    K.gather_walk(np.array([0, 2], np.int32), gmap, "fwd")
    assert K.launch_counts()["gather_walk"] == before + 1


# -- one resident operand per chain ------------------------------------------
def test_one_resident_operand_serves_both_directions():
    # planes of 3 x 89 x 89 bits are over a 1 KiB cap; the store keeps 4 KiB
    idx, sink = _join("left", dup=True, filters=True)
    counters = defaultdict(int)
    store = Q.WalkPlaneStore(counters)
    rng = np.random.default_rng(5)
    walks = [("fwd", "dim", sink), ("bwd", sink, "dim"),
             ("fwd", "dim", sink), ("bwd", sink, "dim")]
    for i, (direction, src, dst) in enumerate(walks):
        rows = _probes(rng, 4, idx.datasets[src].n_rows)
        before = counters["walk_put_bytes"]
        got = Q.fused_walk_record_masks_batch(
            idx, src, dst, rows, direction, max_plane_bytes=1024,
            counters=counters, planes=store)
        np.testing.assert_array_equal(got, _walker(idx, src, dst, rows,
                                                   direction))
        q = max(K.GATHER_BLOCK_Q, 1 << (int(rows.sum()) - 1).bit_length())
        put = counters["walk_put_bytes"] - before
        if i == 0:   # the miss puts the row ids and the operand
            assert (counters["walk_plane_misses"],
                    counters["walk_plane_hits"]) == (1, 0)
            assert put == 4 * q + counters["walk_plane_bytes"]
        else:        # each hit, either way, puts only the row ids
            assert counters["walk_plane_hits"] == i
            assert put == 4 * q
    assert counters["walk_plane_misses"] == 1
    assert counters["gather_walk"] == len(walks)
    assert len(store._stacks) == 1
    (gmap,) = store._stacks.values()
    assert counters["walk_plane_bytes"] == gmap.nbytes > 0


# -- the normal path: prov(...) -> session -> fused walk -> gather route ------
def test_session_routes_a_large_join_through_the_gather_walk():
    """A join whose one dense hop (46,000 x 48,000 bits, 276 MB) exceeds
    the default 256 MiB cap: the session's fused walk answers it from the
    row map, both ways, byte for byte as the host walker."""
    rng = np.random.default_rng(11)
    n_dim, n_fact = 48_000, 46_000
    idx = ProvenanceIndex("gather-session")
    dim = track(_table(rng, n_dim, "k", np.arange(n_dim)), idx, "dim")
    fact = track(_table(rng, n_fact, "k",
                        rng.choice(n_dim, size=n_fact, replace=False)),
                 idx, "fact")
    sink = fact.join(dim, on="k", how="inner").mark_sink().dataset_id
    sess = QuerySession(idx, fused_walk=True)
    host = QuerySession(idx, use_hopcache=False, fused_walk=False)
    for direction, src, dst in (("fwd", "dim", sink), ("bwd", sink, "dim")):
        rows = rng.integers(0, idx.datasets[src].n_rows, size=(64, 4))
        q = prov(idx).source(src).rows_batch(rows)
        q = q.forward() if direction == "fwd" else q.backward()
        plan = q.to(dst).plan()
        got, want = sess.run(plan), host.run(plan)
        assert len(got) == len(want) == 64
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
            assert g.dtype == w.dtype
    c = sess.counters
    assert c["plans"] == c["walk"] == c["fused_walk"] == c["gather_walk"] == 2
    assert c["fused_walk_oversize"] == 0
    assert c["fused_walk_ns"] >= c["gather_walk_ns"] > 0
    assert c["gather_walk_ns"] >= c["walk_put_ns"] + c["walk_wait_ns"]
    assert (c["walk_plane_misses"], c["walk_plane_hits"]) == (1, 1)
    # 1:1 keys: each probe row reaches at most one row, and each plan
    # walks the map's 46,000 links
    assert 0 < c["gather_walk_hits"] <= c["gather_walk_rows"] <= 2 * 64 * 4
    assert c["gather_walk_links"] == 2 * n_fact
    # the federation's entry point gets the dense stack from the same route
    np.testing.assert_array_equal(sess.run_masks(plan), host.run_masks(plan))
    assert c["gather_walk"] == 3


# -- the word scan of a mask stack --------------------------------------------
_RNG = np.random.default_rng(7)
STACKS = {
    "no_probes": np.zeros((0, 5), bool),
    "no_rows": np.zeros((3, 0), bool),
    "all_empty": np.zeros((4, 16), bool),
    "all_set": np.ones((2, 8), bool),
    "size_not_words": _RNG.random((5, 13)) < 0.3,
    "sparse_wide": _RNG.random((64, 6907)) < 0.001,
    "uint8": (_RNG.random((3, 9)) < 0.5).astype(np.uint8),
    "strided": (_RNG.random((6, 40)) < 0.2)[:, ::2],
}


@pytest.mark.parametrize("name", sorted(STACKS))
def test_mask_rows_matches_flatnonzero(name):
    stack = STACKS[name]
    probe, rows = Q.mask_rows(stack)
    want = np.flatnonzero(stack)
    if stack.shape[1]:
        np.testing.assert_array_equal(probe * stack.shape[1] + rows, want)
    else:
        assert len(probe) == len(rows) == 0
    assert rows.dtype == want.dtype


def test_route_answers_no_probes_with_an_empty_list():
    idx, sink = CASES["inner"]()
    stack = np.zeros((0, idx.datasets["dim"].n_rows), dtype=bool)
    got = Q.fused_walk_record_masks_batch(idx, "dim", sink, stack, "fwd",
                                          max_plane_bytes=0, per_probe=True)
    assert got == []
