"""Shared randomized-pipeline generators for the differential parity suites.

One op mix, one seed discipline, one ``-1``-sentinel story (outer joins and
appends) — used by ``test_query_parity``, ``test_structured``,
``test_federation`` and ``test_sharded_parity`` so every engine variant
(walk, hop-cache, structured fast path, federated, sharded) is pinned
against the SAME pipeline distribution.

Two generator families:

* :func:`random_pipeline` / :func:`diamond_pipeline` — build directly into
  one :class:`ProvenanceIndex` (single-index parity suites);
* :func:`random_specs` + :func:`build_merged` / :func:`build_federated` —
  freeze every random choice into a replayable spec list first, so the
  SAME ops can be built merged and split-at-a-boundary
  (federation/sharding seam suites).
"""
import numpy as np

from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.table import Table
from repro.dataprep.tracked import track
from repro.provenance import ProvCatalog
from repro.provenance.catalog import qualify


# ===========================================================================
# Randomized pipelines over every op category
# ===========================================================================
def random_pipeline(seed, name="parity", scale=1):
    """3-8 random ops over identity/vreduce/vaugment/hreduce/haugment/join/
    append, including outer joins and appends (``-1`` sentinels).  Returns
    ``(index, sink_dataset_id, rng)`` — the rng is advanced past the build
    so callers draw independent probes.  ``scale`` multiplies the source's
    15-49 rows (and with them the join key range)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(15, 50)) * scale
    K = max(3, n // 4)
    idx = ProvenanceIndex(f"{name}{seed}")
    t = Table.from_columns({
        "k": rng.integers(0, K, n).astype(np.float32),
        "x": rng.normal(size=n).astype(np.float32),
        "g": rng.integers(0, 4, n).astype(np.float32),
        "y": rng.normal(size=n).astype(np.float32),
    })
    cur = track(t, idx, "src")
    n_ops = int(rng.integers(3, 8))
    for i in range(n_ops):
        code = int(rng.integers(0, 9))
        cols = cur.table.columns
        if code == 0:
            mask = np.asarray(cur.table.col("x")) > float(rng.normal(-1.0, 0.4))
            if not mask.any():
                mask[0] = True
            cur = cur.filter_rows(mask)
        elif code == 1:
            cur = cur.value_transform("x", "scale", factor=2.0)
        elif code == 2:
            cur = cur.oversample(frac=0.3, seed=int(rng.integers(1 << 20)))
        elif code == 3:
            cur = cur.undersample(frac=0.7, seed=int(rng.integers(1 << 20)))
        elif code == 4 and "g" in cols:
            cur = cur.onehot("g", n_values=4)
        elif code == 5:
            # order-changing vreduce: keep k/x/g, shuffle, maybe drop y
            keep = [c for c in cols if c in ("k", "x", "g")]
            extra = [c for c in cols if c not in ("k", "x", "g")]
            rng.shuffle(keep)
            keep += list(rng.choice(extra, size=len(extra) // 2, replace=False)) \
                if extra else []
            cur = cur.select_columns(keep)
        elif code == 6:
            r = Table.from_columns({
                "k": np.arange(K, dtype=np.float32),
                f"z{i}": rng.normal(size=K).astype(np.float32),
            })
            how = str(rng.choice(["inner", "outer"]))
            cur = cur.join(track(r, idx), on="k", how=how)
        elif code == 7:
            m = int(rng.integers(3, 9))
            r = Table.from_columns({
                "x": rng.normal(size=m).astype(np.float32),
                f"w{i}": rng.normal(size=m).astype(np.float32),
            })
            cur = cur.append(track(r, idx))
        elif code == 8 and "y" in cols:
            cur = cur.drop_columns(["y"])
        if cur.table.n_rows == 0:
            break
    cur.mark_sink()
    return idx, cur.dataset_id, rng


def row_probes(rng, n):
    """The canonical probe triple: empty, single row, small sorted set."""
    probes = [[], [int(rng.integers(0, n))],
              sorted(set(rng.integers(0, n, size=min(5, n)).tolist()))]
    return probes


def diamond_pipeline(seed=0, name="diamond"):
    """src feeds two branches re-joined downstream — TWO producer paths, the
    shape the old unique-chain hop-cache could not compose."""
    rng = np.random.default_rng(seed)
    idx = ProvenanceIndex(f"{name}{seed}")
    n = int(rng.integers(8, 20))
    t = Table.from_columns({
        "k": np.arange(n, dtype=np.float32),
        "x": rng.normal(size=n).astype(np.float32),
    })
    s = track(t, idx, "src")
    a = s.filter_rows(rng.random(n) < 0.75)
    b = s.value_transform("x", "scale", factor=2.0)
    j = a.join(b, on="k", how="inner").mark_sink()
    return idx, j.dataset_id


# ===========================================================================
# Spec-replay pipelines: ONE op list, built merged and split
# ===========================================================================
def random_specs(seed):
    """A replayable op-spec list (every random choice frozen into the spec,
    so the merged and the federated build apply IDENTICAL ops)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(15, 40))
    K = max(3, n // 4)
    base = {
        "k": rng.integers(0, K, n).astype(np.float32),
        "x": rng.normal(size=n).astype(np.float32),
        "g": rng.integers(0, 4, n).astype(np.float32),
    }
    specs = []
    for i in range(int(rng.integers(4, 8))):
        code = int(rng.integers(0, 6))
        if code == 0:
            specs.append(("filter", float(rng.normal(-1.0, 0.4))))
        elif code == 1:
            specs.append(("scale",))
        elif code == 2:
            specs.append(("oversample", 0.3, int(rng.integers(1 << 20))))
        elif code == 3:
            specs.append(("undersample", 0.7, int(rng.integers(1 << 20))))
        elif code == 4:
            ref = {
                "k": np.arange(K, dtype=np.float32),
                f"z{i}": rng.normal(size=K).astype(np.float32),
            }
            specs.append(("join", ref, str(rng.choice(["inner", "outer"]))))
        else:
            m = int(rng.integers(3, 9))
            ref = {
                "x": rng.normal(size=m).astype(np.float32),
                f"w{i}": rng.normal(size=m).astype(np.float32),
            }
            specs.append(("append", ref))
    return base, specs


def apply_spec(cur, spec, idx):
    kind = spec[0]
    if kind == "filter":
        mask = np.asarray(cur.table.col("x")) > spec[1]
        if not mask.any():
            mask[0] = True
        return cur.filter_rows(mask)
    if kind == "scale":
        return cur.value_transform("x", "scale", factor=2.0)
    if kind == "oversample":
        return cur.oversample(frac=spec[1], seed=spec[2])
    if kind == "undersample":
        return cur.undersample(frac=spec[1], seed=spec[2])
    if kind == "join":
        r = track(Table.from_columns({c: v.copy() for c, v in spec[1].items()}), idx)
        return cur.join(r, on="k", how=spec[2])
    if kind == "append":
        r = track(Table.from_columns({c: v.copy() for c, v in spec[1].items()}), idx)
        return cur.append(r)
    raise ValueError(kind)


def build_merged(base, specs):
    idx = ProvenanceIndex("merged")
    cur = track(Table.from_columns({c: v.copy() for c, v in base.items()}),
                idx, "src")
    ids = ["src"]
    for spec in specs:
        cur = apply_spec(cur, spec, idx)
        ids.append(cur.dataset_id)
    cur.mark_sink()
    return idx, ids


def build_federated(base, specs, cut):
    """Split the SAME spec list at ``cut``: prep owns ops [0, cut), serve
    owns ops [cut, ...) over a source holding the boundary table, glued by
    an identity link.  Returns the catalog plus the merged-id -> qualified
    ref mapping aligned with ``build_merged``'s ``ids``."""
    prep = ProvenanceIndex("prep")
    cur = track(Table.from_columns({c: v.copy() for c, v in base.items()}),
                prep, "src")
    refs = [qualify("prep", "src")]
    for spec in specs[:cut]:
        cur = apply_spec(cur, spec, prep)
        refs.append(qualify("prep", cur.dataset_id))
    boundary = cur.dataset_id
    serve = ProvenanceIndex("serve")
    scur = track(cur.table, serve, "ingest")
    for spec in specs[cut:]:
        scur = apply_spec(scur, spec, serve)
        refs.append(qualify("serve", scur.dataset_id))
    scur.mark_sink()
    catalog = ProvCatalog(f"fed-cut{cut}")
    catalog.register("prep", prep).register("serve", serve)
    catalog.link(qualify("prep", boundary), "serve/ingest")
    return catalog, refs, qualify("serve", scur.dataset_id)


def cross_boundary_diamond(seed=0):
    """Two links carry two branches of one source across the boundary —
    BOTH must contribute or the answer under-counts."""
    rng = np.random.default_rng(seed)
    base = {
        "k": np.arange(12, dtype=np.float32),
        "x": rng.normal(size=12).astype(np.float32),
    }
    keep = rng.random(12) < 0.75
    if not keep.any():
        keep[0] = True

    merged = ProvenanceIndex("merged")
    s = track(Table.from_columns({c: v.copy() for c, v in base.items()}),
              merged, "src")
    a = s.filter_rows(keep)
    b = s.value_transform("x", "scale", factor=2.0)
    j = a.join(b, on="k", how="inner").mark_sink()

    prep = ProvenanceIndex("prep")
    ps = track(Table.from_columns({c: v.copy() for c, v in base.items()}),
               prep, "src")
    pa = ps.filter_rows(keep)
    pb = ps.value_transform("x", "scale", factor=2.0)
    serve = ProvenanceIndex("serve")
    sa = track(pa.table, serve, "branch_a")
    sb = track(pb.table, serve, "branch_b")
    sj = sa.join(sb, on="k", how="inner").mark_sink()

    catalog = ProvCatalog("diamond")
    catalog.register("prep", prep).register("serve", serve)
    catalog.link(qualify("prep", pa.dataset_id), "serve/branch_a")
    catalog.link(qualify("prep", pb.dataset_id), "serve/branch_b")
    return merged, j.dataset_id, catalog, qualify("serve", sj.dataset_id)
