"""Ref-vs-pallas parity suite for the fused migration kernels (DESIGN.md §9).

The contract under test: every executor of the fused superstep path —
the pure-jax oracle ("jax"), the Pallas kernel under ``interpret=True``
and (on TPU) the native kernel — produces **bit-identical** partition
assignments, pending moves and statistics to the unfused reference
pipeline in ``core/migration.py``, on any graph, because the counts are
exact integers, the RNG draws are shared and argmax tie handling matches.

Runs under hypothesis when installed; otherwise the deterministic
fixed-seed fallback sampler (``tests/_hypothesis_fallback.py``) replays
the same properties.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                     # pragma: no cover
    from _hypothesis_fallback import given, settings, st

from repro.api import DynamicGraphSystem, SystemConfig
from repro.api.config import ComputeSection, PartitionSection
from repro.core import initial_partition, make_state, occupancy
from repro.core.migration import (_rank_within_group, _rank_within_group_fast,
                                  migrate_step, neighbour_partition_counts)
from repro.core.repartitioner import adapt_jit, run_to_convergence
from repro.graph import generators
from repro.graph.bsr import graph_to_bsr
from repro.graph.structure import Graph, from_edges
from repro.kernels import migration_kernels, ref
from repro.kernels.bsr_spmm import max_tiles_per_row
from repro.kernels.migration_kernels import (MigrationPlan, build_plan,
                                             label_histogram,
                                             pallas_score_select,
                                             score_select)

KEY = jax.random.PRNGKey(0)


def _random_graph(n: int, seed: int, kind: str) -> Graph:
    if kind == "fem":
        side = max(2, round(n ** (1 / 3)))
        return generators.fem_cube(side)
    if kind == "plc":
        return generators.power_law(max(n, 10), seed=seed)
    # sparse random COO with dead padding slots
    rng = np.random.default_rng(seed)
    m = max(1, 2 * n)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    return from_edges(src, dst, n, n_cap=n + 7, e_cap=m + 5)


# ---------------------------------------------------------------------------
# histogram parity: core ref / flat / ELL / BSR oracle / interpret kernel
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(20, 90), st.integers(0, 4), st.integers(2, 11),
       st.sampled_from(["fem", "plc", "coo"]))
def test_histogram_parity_random_graphs(n, seed, k, kind):
    g = _random_graph(n, seed, kind)
    lab = initial_partition(g, k, "hsh")
    want = np.asarray(neighbour_partition_counts(g, lab, k))
    for executor, plan in (("jax", None),
                           ("jax", build_plan(g, executor="jax")),
                           ("interpret", build_plan(g, executor="interpret",
                                                    blk=8))):
        got = np.asarray(label_histogram(g, plan, lab, k, executor=executor))
        kindname = plan.kind if plan is not None else "flat"
        np.testing.assert_array_equal(
            got, want, err_msg=f"executor={executor} plan={kindname}")


def test_histogram_padded_and_empty_tiles():
    """Padding tiles (block_cols == -1, nnzb_cap > nnzb) and empty row
    blocks must contribute nothing, in the kernel and in its oracle."""
    g = generators.fem_grid2d(5, n_cap=40, e_cap=80)   # 25 live of 40 slots
    k = 4
    lab = initial_partition(g, k, "hsh")
    bsr = graph_to_bsr(g, blk=8, nnzb_cap=64)          # heavy tile padding
    plan = MigrationPlan(kind="bsr", blocks=bsr.blocks,
                         block_cols=bsr.block_cols, row_ptr=bsr.row_ptr,
                         max_per_row=max_tiles_per_row(np.asarray(bsr.row_ptr)))
    want = np.asarray(neighbour_partition_counts(g, lab, k))
    got = np.asarray(label_histogram(g, plan, lab, k, executor="interpret"))
    np.testing.assert_array_equal(got, want)
    # an all-padding (edgeless) graph: counts identically zero
    g0 = Graph(src=jnp.full((16,), -1, jnp.int32),
               dst=jnp.full((16,), -1, jnp.int32),
               node_mask=jnp.zeros((24,), bool),
               edge_mask=jnp.zeros((16,), bool))
    got0 = np.asarray(label_histogram(g0, None, jnp.zeros((24,), jnp.int32),
                                      k, executor="jax"))
    assert (got0 == 0).all()


def test_score_select_parity_all_executors():
    """Fused decide+damp epilogue: targets/willing/gain identical across
    the oracle and the interpret-mode kernel, both tie-break rules."""
    g = generators.fem_cube(6)
    n, k = g.n_cap, 5
    lab = initial_partition(g, k, "hsh")
    keys = jax.random.split(KEY, 2)
    noise = jax.random.uniform(keys[0], (n, k))
    gate = jax.random.bernoulli(keys[1], p=0.5, shape=(n,))
    plan_bsr = build_plan(g, executor="interpret", blk=8)
    for tie in ("random", "stay"):
        base = None
        for executor, plan in (("jax", None),
                               ("jax", build_plan(g, executor="jax")),
                               ("interpret", plan_bsr)):
            out = score_select(g, plan, lab, g.node_mask, noise, gate, k,
                               tie_break=tie, executor=executor)
            out = tuple(np.asarray(x) for x in out)
            if base is None:
                base = out
                continue
            for name, a, b in zip(("counts", "target", "willing", "gain"),
                                  base, out):
                np.testing.assert_array_equal(
                    a, b, err_msg=f"{tie}/{executor}/{name}")


def test_bsr_oracle_matches_kernel():
    """kernels/ref.py oracle of the BSR histogram == the interpret kernel
    on the same packed tiles (the per-kernel contract of DESIGN.md §9)."""
    g = generators.power_law(60, seed=2)
    k = 6
    lab = initial_partition(g, k, "hsh")
    bsr = graph_to_bsr(g, blk=8, nnzb_cap=None)
    n_pad = bsr.n_blocks * 8
    lab_pad = jnp.pad(lab, (0, n_pad - g.n_cap), constant_values=-1)
    want = np.asarray(ref.ref_bsr_label_histogram(
        bsr.blocks, bsr.block_cols, bsr.row_ptr, lab_pad, k))
    counts, _, _, _ = pallas_score_select(
        bsr.blocks, bsr.block_cols, bsr.row_ptr, lab_pad,
        jnp.ones((n_pad,), bool), jnp.zeros((n_pad, k), jnp.float32),
        jnp.zeros((n_pad,), bool), k=k,
        max_per_row=max_tiles_per_row(np.asarray(bsr.row_ptr)),
        tie_break="stay", interpret=True)
    np.testing.assert_array_equal(np.asarray(counts), want)


# ---------------------------------------------------------------------------
# full-step parity: the acceptance criterion (identical assignments)
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(25, 100), st.integers(0, 4), st.integers(2, 9),
       st.sampled_from(["random", "stay"]), st.sampled_from(["fem", "plc"]))
def test_migrate_step_backend_parity(n, seed, k, tie, kind):
    g = _random_graph(n, seed, kind)
    lab = initial_partition(g, k, "hsh")
    st_ref = st_fused = make_state(g, lab, k, slack=0.2, seed=seed)
    plan = build_plan(g, executor="jax")
    for _ in range(5):
        st_ref, stats_ref = migrate_step(st_ref, g, s=0.5, tie_break=tie,
                                         backend="ref")
        st_fused, stats_fused = migrate_step(st_fused, g, plan, s=0.5,
                                             tie_break=tie, backend="pallas",
                                             executor="jax")
        np.testing.assert_array_equal(np.asarray(st_ref.assignment),
                                      np.asarray(st_fused.assignment))
        np.testing.assert_array_equal(np.asarray(st_ref.pending),
                                      np.asarray(st_fused.pending))
        assert all(int(a) == int(b) for a, b
                   in zip(stats_ref, stats_fused))


def test_migrate_step_interpret_kernel_parity():
    """The actual Pallas kernel (interpret mode) inside migrate_step."""
    g = generators.fem_cube(5)
    k = 4
    lab = initial_partition(g, k, "hsh")
    st_ref = st_k = make_state(g, lab, k, slack=0.2, seed=1)
    plan = build_plan(g, executor="interpret", blk=8)
    for _ in range(3):
        st_ref, _ = migrate_step(st_ref, g, s=0.5, backend="ref")
        st_k, _ = migrate_step(st_k, g, plan, s=0.5, backend="pallas",
                               executor="interpret")
        np.testing.assert_array_equal(np.asarray(st_ref.assignment),
                                      np.asarray(st_k.assignment))


def test_driver_parity_adapt_and_converge():
    """The jit'd superstep (lax.scan) and the convergence driver agree
    across backends end to end."""
    g = generators.fem_cube(7)
    k = 6
    lab = initial_partition(g, k, "hsh")
    state = make_state(g, lab, k, slack=0.2, seed=3)
    plan = build_plan(g, executor="jax")

    a = adapt_jit(g, state, s=0.5, iters=6, backend="ref")
    b = adapt_jit(g, state, s=0.5, iters=6, backend="pallas", plan=plan)
    np.testing.assert_array_equal(np.asarray(a.assignment),
                                  np.asarray(b.assignment))

    sa, ha = run_to_convergence(g, state, max_iters=40, patience=10,
                                backend="ref")
    sb, hb = run_to_convergence(g, state, max_iters=40, patience=10,
                                backend="pallas", plan=plan)
    np.testing.assert_array_equal(np.asarray(sa.assignment),
                                  np.asarray(sb.assignment))
    assert ha.migrations == hb.migrations
    assert ha.cut_ratio == hb.cut_ratio


@pytest.mark.parametrize("kind, budget, want", [
    ("fem", None, "bsr"),           # 6^3 mesh: one 16 KiB tile row per block
    ("plc", 4096, "flat"),          # a single 64x64 tile is over 4 KiB
])
def test_batch_plan_respects_device_budget(monkeypatch, kind, budget, want):
    """On the Pallas executors a BSR pack over the device-memory budget is
    refused before it is allocated and the batch drivers score over the
    flat plan instead. The session reports the kind, and the assignments
    equal the reference path's either way."""
    monkeypatch.setenv("REPRO_PALLAS_EXECUTOR", "interpret")
    if budget is not None:
        monkeypatch.setattr(migration_kernels, "plan_memory_budget",
                            lambda: budget)
    g = _random_graph(216, 1, kind)
    labels = {}
    for backend in ("pallas", "ref"):
        cfg = SystemConfig(partition=PartitionSection(strategy="xdgp", k=4),
                           compute=ComputeSection(backend=backend), seed=2)
        system = DynamicGraphSystem(g, cfg)
        system.adapt(3)
        labels[backend] = np.asarray(system.labels)
        plan = system.scoring_plan
        if backend == "pallas":
            assert plan["kind"] == want
            assert system.snapshot()["scoring_plan"] == plan
        else:
            assert plan is None
    np.testing.assert_array_equal(labels["pallas"], labels["ref"])


# ---------------------------------------------------------------------------
# capacity invariant + full partitions under the fused path
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(25, 100), st.integers(0, 4), st.integers(2, 8))
def test_fused_migration_preserves_capacity_invariant(n, seed, k):
    """Quotas under the fused path guarantee occupancy never grows past
    max(initial, capacity) — same invariant the ref path holds."""
    g = generators.power_law(n, seed=seed)
    state = make_state(g, initial_partition(g, k, "hsh"), k, slack=0.2,
                       seed=seed)
    cap = int(np.asarray(state.capacity)[0])
    bound = max(cap, int(np.asarray(occupancy(state, g.node_mask)).max()))
    plan = build_plan(g, executor="jax")
    for _ in range(6):
        state, _ = migrate_step(state, g, plan, s=0.5, backend="pallas",
                                executor="jax")
        a = np.asarray(state.assignment)
        assert ((a >= 0) & (a < k)).all()
        assert int(np.asarray(occupancy(state, g.node_mask)).max()) <= bound


def test_full_partitions_admit_nothing():
    """With zero free capacity everywhere, the quota is zero and the fused
    step must not admit a single move."""
    g = generators.fem_cube(5)
    k = 5
    lab = initial_partition(g, k, "hsh")
    state = make_state(g, lab, k, seed=0)
    occ = occupancy(state, g.node_mask)
    state = state.__class__(assignment=state.assignment, pending=state.pending,
                            capacity=occ.astype(jnp.int32), rng=state.rng,
                            iteration=state.iteration,
                            last_moves=state.last_moves)
    for backend in ("ref", "pallas"):
        st2, stats = migrate_step(state, g, s=1.0, backend=backend)
        assert int(stats.admitted) == 0
        assert (np.asarray(st2.pending) == -1).all()


# ---------------------------------------------------------------------------
# quota ranking: the fast path is bit-identical to the stable sort
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(st.integers(1, 400), st.integers(1, 100), st.integers(0, 6),
       st.floats(0.0, 1.0))
def test_rank_within_group_fast_matches_stable(n, num_groups, seed, density):
    rng = np.random.default_rng(seed)
    group = jnp.asarray(rng.integers(0, num_groups, n).astype(np.int32))
    active = jnp.asarray(rng.random(n) < density)
    slow = np.asarray(_rank_within_group(group, active, num_groups))
    fast = np.asarray(_rank_within_group_fast(group, active,
                                              num_groups=num_groups))
    np.testing.assert_array_equal(slow, fast)
    # both against the definition: id-order count of earlier active members
    g, a = np.asarray(group), np.asarray(active)
    want = np.zeros(n, np.int32)
    for j in np.unique(g[a]):
        idx = np.flatnonzero(a & (g == j))
        want[idx] = np.arange(idx.size)
    np.testing.assert_array_equal(slow, want)
