"""Vector cluster backend: grouping/straggler layout, the ServerSpec
engine knob, empty-tick behaviour, and the unsupported-feature gates.

Bit-exactness against the object engines is asserted in
``tests/test_agreement.py``; these are the structural edges the spec
layer and benchmarks rely on."""
import numpy as np
import pytest

from repro.core.spec import (ExperimentSpec, ServerSpec, TickWorkloadSpec,
                             run_experiment)
from repro.serving import ClusterConfig, Request, VectorCluster
from repro.serving.vector_cluster import _VectorGroup  # noqa: F401


def make_vc(specs, policy="least-outstanding"):
    return VectorCluster(specs, ClusterConfig(policy=policy))


# ---------------------------------------------------------------------------
# Grouping: homogeneous specs coalesce, everything else falls back
# ---------------------------------------------------------------------------


def test_homogeneous_servers_form_one_group():
    vc = make_vc([ServerSpec(cores=4)] * 8)
    s = vc.summary()
    assert s["backend"] == "vector"
    assert len(s["groups"]) == 1
    assert s["groups"][0]["members"] == list(range(8))
    assert s["stragglers"] == []


def test_mixed_shapes_group_by_identical_config():
    vc = make_vc([ServerSpec(cores=6), ServerSpec(cores=6),
                  ServerSpec(cores=2, scheduler="cfs"),
                  ServerSpec(cores=2, scheduler="cfs"),
                  ServerSpec(cores=4, scheduler="fifo"),      # fallback
                  ServerSpec(cores=6, engine="object")])      # pinned
    s = vc.summary()
    members = sorted(tuple(g["members"]) for g in s["groups"])
    assert members == [(0, 1), (2, 3)]
    assert s["stragglers"] == [4, 5]


def test_vector_knob_rejects_unvectorizable_scheduler():
    with pytest.raises(ValueError, match="not vectorizable"):
        make_vc([ServerSpec(cores=4, scheduler="srtf", engine="vector")])


def test_engine_knob_validated_on_spec():
    with pytest.raises(ValueError, match="unknown server engine"):
        ServerSpec(engine="warp")
    with pytest.raises(ValueError, match="DES-only"):
        ExperimentSpec(engine="vector", dispatch_latency=0.5)


# ---------------------------------------------------------------------------
# Empty ticks: no arrivals, all lanes idle
# ---------------------------------------------------------------------------


def test_empty_ticks_are_inert():
    """Ticking an idle vector cluster advances time and nothing else —
    and the cluster still serves correctly afterwards."""
    vc = make_vc([ServerSpec(cores=2, slots=8)] * 3)
    vc.tick_log = []
    for _ in range(50):
        vc.tick(())
    assert vc.t == 50 and len(vc.tick_log) == 50
    assert vc._finished_count() == 0
    assert all(qlen == 0 and actives == (0, 0, 0)
               for _, qlen, actives in vc.tick_log)
    g = vc.groups[0]
    assert g.filter_count.sum() == 0 and g.cfs_count.sum() == 0
    assert g.outstanding.sum() == 0
    assert (g.free_slots == 8).all()
    assert (g.S == 32).all()                      # adaptive S untouched
    # a request arriving after the idle stretch completes normally
    vc.tick([Request(rid=0, arrival=vc.t, prompt_len=4, n_tokens=3)])
    for _ in range(10):
        vc.tick(())
    done = vc._collect()
    assert [r.rid for r in done] == [0]
    assert done[0].finish == 50 + 4               # prefill + 3 decode ticks
    assert done[0].served_ticks == 4


def test_empty_tick_on_cfs_group():
    vc = make_vc([ServerSpec(cores=2, scheduler="cfs")] * 2)
    for _ in range(10):
        vc.tick(())
    assert vc._finished_count() == 0
    assert vc.groups[0].min_vruntime.tolist() == [0.0, 0.0]


# ---------------------------------------------------------------------------
# Unsupported features gate cleanly
# ---------------------------------------------------------------------------


def test_stall_events_rejected_on_vector_path():
    vc = make_vc([ServerSpec(cores=2)])
    req = Request(rid=0, arrival=0, prompt_len=4, n_tokens=8,
                  stall_events=((2, 3),))
    with pytest.raises(ValueError, match="stall events"):
        vc.tick([req])


def test_stall_events_ok_on_pinned_object_server():
    vc = make_vc([ServerSpec(cores=2, engine="object")])
    req = Request(rid=0, arrival=0, prompt_len=4, n_tokens=8,
                  stall_events=((2, 3),))
    done = vc.run([req], max_ticks=1000)
    assert done[0].finish is not None and done[0].n_ctx >= 1


# ---------------------------------------------------------------------------
# run_experiment plumbing
# ---------------------------------------------------------------------------


def test_run_experiment_vector_engine_end_to_end():
    res = run_experiment(ExperimentSpec(
        engine="vector", servers=tuple(ServerSpec(cores=4)
                                       for _ in range(16)),
        dispatch="sfs-aware", workload=TickWorkloadSpec(n=600, load=0.9,
                                                        seed=9)))
    assert res.engine == "vector" and res.unit == "t"
    assert res.n == 600
    assert res.rids.tolist() == list(range(600))
    assert sum(res.dispatch_counts) == 600
    assert np.all(res.finish > 0)
    assert res.buckets()
