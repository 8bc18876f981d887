"""Property-based tests (hypothesis) for the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r requirements-dev.txt)")
from hypothesis import HealthCheck, given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.checkpoint import restore_pytree, save_pytree
from repro.core import (
    ContainerRequest,
    JobSpec,
    Resource,
    ResourceManager,
    TaskSpec,
    Node,
    build_cluster_spec,
    parse_tony_xml,
    to_tony_xml,
)
from repro.core.cluster_spec import TaskAddress
from repro.core.rm import AllocationError
from repro.distributed.sharding import RULES, spec_for

SETTINGS = settings(max_examples=40, deadline=None,
                    suppress_health_check=[HealthCheck.too_slow])


# ----------------------------------------------------------------------
# RM: resource conservation under arbitrary alloc/release interleavings

ops_strategy = st.lists(
    st.tuples(st.sampled_from(["alloc", "release"]),
              st.integers(0, 3),            # node-label choice / release idx
              st.integers(1, 4000),         # memory
              st.integers(0, 4)),           # gpus
    min_size=1, max_size=60)


@SETTINGS
@given(ops_strategy)
def test_rm_conservation_under_random_ops(ops):
    nodes = [Node("g0", Resource(8000, 64, 4), frozenset({"gpu"})),
             Node("g1", Resource(8000, 64, 4), frozenset({"gpu"})),
             Node("c0", Resource(16000, 64, 0), frozenset({"highmem"}))]
    rm = ResourceManager(nodes)
    app = rm.submit_application("prop", "default")
    live = []
    for kind, sel, mem, gpus in ops:
        if kind == "alloc":
            label = ["gpu", "highmem", None, None][sel]
            try:
                c = rm.allocate(app, ContainerRequest(Resource(mem, 1, gpus), label))
                live.append(c)
                if label:
                    assert label in rm.nodes[c.node_id].labels
            except AllocationError:
                pass
        elif live:
            c = live.pop(sel % len(live))
            rm.release(c.container_id)
        assert rm.invariants_ok()
    for n in rm.nodes.values():
        assert n.used.nonnegative and n.used.fits_in(n.capacity)


# ----------------------------------------------------------------------
# Cluster spec: permutation-invariant, ordered by index

@SETTINGS
@given(st.permutations(list(range(6))), st.integers(1, 4))
def test_cluster_spec_order_invariant(perm, n_ps):
    addrs = ([TaskAddress("worker", i, f"h{i}", 1000 + i) for i in range(6)]
             + [TaskAddress("ps", i, f"p{i}", 2000 + i) for i in range(n_ps)])
    shuffled = [addrs[i] for i in perm] + addrs[6:]
    spec = build_cluster_spec(shuffled)
    assert spec["worker"] == [f"h{i}:{1000+i}" for i in range(6)]
    assert spec["ps"] == [f"p{i}:{2000+i}" for i in range(n_ps)]


# ----------------------------------------------------------------------
# XML round trip for arbitrary job specs

names = st.text(alphabet="abcdefgh", min_size=1, max_size=8)


@SETTINGS
@given(st.dictionaries(
    st.sampled_from(["worker", "ps", "chief", "evaluator"]),
    st.tuples(st.integers(1, 16), st.integers(128, 1 << 20),
              st.integers(1, 64), st.integers(0, 8),
              st.sampled_from([None, "gpu", "highmem"])),
    min_size=1, max_size=4), names)
def test_xml_roundtrip_property(tasks, name):
    spec = JobSpec(name=name, tasks={
        t: TaskSpec(t, inst, Resource(mem, vc, gp), lbl)
        for t, (inst, mem, vc, gp, lbl) in tasks.items()})
    again = parse_tony_xml(to_tony_xml(spec))
    assert set(again.tasks) == set(spec.tasks)
    for t, ts in spec.tasks.items():
        at = again.tasks[t]
        assert (at.instances, at.resource, at.node_label) == \
            (ts.instances, ts.resource, ts.node_label)


# ----------------------------------------------------------------------
# Checkpoint: save/restore is identity for arbitrary nested pytrees

leaf = st.tuples(st.integers(1, 4), st.integers(1, 4)).map(
    lambda s: np.random.default_rng(0).normal(size=s).astype(np.float32))
trees = st.recursive(
    leaf, lambda ch: st.dictionaries(names, ch, min_size=1, max_size=3),
    max_leaves=8)


@SETTINGS
@given(trees, st.integers(0, 10 ** 7))
def test_checkpoint_roundtrip_property(tree, step):
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        save_pytree(tree, d, step)
        back = restore_pytree(jax.tree.map(lambda x: x, tree), d, step)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------
# Checkpoint commit protocol: arbitrary kill points never expose a partial
# step (latest_step only ever names a fully committed directory)


class _ModuleProxy:
    """A module stand-in with chosen attributes overridden — patches the
    checkpointer module's view only, not numpy/json/os globally."""

    def __init__(self, mod, **overrides):
        self._mod = mod
        self._overrides = overrides

    def __getattr__(self, name):
        if name in self._overrides:
            return self._overrides[name]
        return getattr(self._mod, name)


class _Killed(RuntimeError):
    pass


@SETTINGS
@given(st.integers(0, 3), st.integers(2, 10 ** 6))
def test_checkpoint_kill_point_never_corrupts_latest(kill_op, step):
    """kill_op: 0 = no kill, 1 = during a leaf file's write, 2 = during
    COMMIT write, 3 = at the atomic rename. The kill leaves all debris in place (a
    hard kill runs no finally). Invariant: latest_step names the new step
    iff every op completed; otherwise the previous checkpoint is intact."""
    import json
    import os
    import shutil
    import tempfile

    import repro.checkpoint.checkpointer as ck

    def killer(*a, **k):
        raise _Killed

    tree1 = {"w": np.ones((3,), np.float32)}
    tree2 = {"w": np.full((3,), 7.0, np.float32)}
    with tempfile.TemporaryDirectory() as d:
        save_pytree(tree1, d, 1)
        saved = (ck._write_leaf, ck.json, ck.os, ck.shutil)
        try:
            if kill_op == 1:
                ck._write_leaf = killer
            elif kill_op == 2:
                ck.json = _ModuleProxy(json, dump=killer)
            elif kill_op == 3:
                ck.os = _ModuleProxy(os, replace=killer)
            if kill_op:
                ck.shutil = _ModuleProxy(shutil,
                                         rmtree=lambda *a, **k: None)
                with pytest.raises(_Killed):
                    ck.save_pytree(tree2, d, step)
            else:
                ck.save_pytree(tree2, d, step)
        finally:
            ck._write_leaf, ck.json, ck.os, ck.shutil = saved
        if kill_op:
            assert ck.latest_step(d) == 1
            assert not ck.is_committed(d, step)
            back = restore_pytree({"w": np.zeros((3,), np.float32)}, d)
            np.testing.assert_array_equal(back["w"], tree1["w"])
        else:
            assert ck.latest_step(d) == step
            back = restore_pytree({"w": np.zeros((3,), np.float32)}, d, step)
            np.testing.assert_array_equal(back["w"], tree2["w"])


# ----------------------------------------------------------------------
# Sharding rules: produced specs always divide the dims they shard

axes_st = st.lists(st.sampled_from(["embed", "mlp", "heads", "kv_heads",
                                    "vocab", "experts", "lru", None]),
                   min_size=1, max_size=4)
dims_st = st.lists(st.sampled_from([1, 2, 8, 16, 24, 32, 64, 100, 256, 4096]),
                   min_size=1, max_size=4)


@SETTINGS
@given(axes_st, dims_st, st.sampled_from(list(RULES)))
def test_sharding_specs_always_divisible(axes, dims, strategy):
    import os
    n = min(len(axes), len(dims))
    axes, dims = tuple(axes[:n]), tuple(dims[:n])

    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}

    spec = spec_for(axes, dims, FakeMesh(), RULES[strategy],
                    max_shardings=1 if strategy == "ps" else None)
    used = []
    for entry, dim in zip(tuple(spec), dims):
        if entry is None:
            continue
        parts = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for p in parts:
            size *= FakeMesh.shape[p]
            used.append(p)
        assert dim % size == 0
    assert len(used) == len(set(used))  # no mesh axis reused in one param
