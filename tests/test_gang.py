"""Gang scheduling (core/gang/ + the cluster's all-or-nothing admission
path): parallelism descriptors, the comms cost model that makes co-located
slice sets strictly cheaper than scattered ones, the placement search, and
the event-loop integration — gang-wide re-queue on member failure, the
full/incremental re-timing equivalence, and the gang_pipeline scenario's
co-located > scattered goodput verdict."""
import dataclasses

import pytest

from repro.core.cluster import Cluster
from repro.core.elastic import split_by_failure
from repro.core.gang.comms import (
    DEFAULT_LINK,
    LinkModel,
    comm_overhead_s,
    gang_step_s,
    placement_spread,
    ring_links,
)
from repro.core.gang.parallelism import (
    PARALLELISMS,
    Parallelism,
    axis_rank_groups,
    gang_of_member,
    gang_world_size,
    is_gang,
    member_memory_fraction,
    member_name,
    rank_coords,
    resolve_parallelism,
)
from repro.core.gang.placement import plan_gang, split_counts
from repro.core.instance import JobSpec
from repro.core.sharing import CollocationMode
from repro.core.workload import train_workload
from repro.launch.simulate import (
    GANG_FLEET_SKUS,
    SIM_SAMPLES_PER_EPOCH,
    SIM_SUITE,
    make_trace,
    run_cell,
    summarize_cell,
    synthetic_sku_dbs,
)
from repro.launch.simulate import main as simulate_main

TP2 = Parallelism(tensor=2)
TP2PP2 = Parallelism(tensor=2, pipeline=2)

_DBS = synthetic_sku_dbs(GANG_FLEET_SKUS)


def gang_train(name, arch, par, **kw):
    """A phase-aware training gang over a registry arch (the helpers build
    singletons; a gang is the same workload plan, wider)."""
    return dataclasses.replace(
        train_workload(name, arch, SIM_SUITE, **kw),
        world_size=par.world_size,
        parallelism=par,
    )


def fleet(n, sku="a100-80gb", mode="mig"):
    return [(f"d{i}", mode, sku) for i in range(n)]


# -- parallelism descriptors -------------------------------------------------------


def test_descriptor_axes_label_and_world_size():
    assert TP2PP2.world_size == 4 and TP2PP2.model_degree == 4
    assert TP2PP2.label == "tp2.pp2.dp1"
    dp = Parallelism(data=4)
    assert dp.world_size == 4 and dp.model_degree == 1
    with pytest.raises(ValueError):
        Parallelism(tensor=0)


def test_resolve_parallelism_every_spelling():
    assert resolve_parallelism("tp2.pp2") == TP2PP2  # registry name
    assert resolve_parallelism(TP2) is TP2  # descriptor passthrough
    job = gang_train("g", "stablelm-12b", TP2)
    assert resolve_parallelism(job) == TP2  # job carrying one
    bare = dataclasses.replace(
        train_workload("b", "stablelm-12b", SIM_SUITE), world_size=3
    )
    assert resolve_parallelism(bare) == Parallelism(data=3)  # conservative DP
    with pytest.raises(KeyError, match="tp2.pp2"):  # lists registered names
        resolve_parallelism("tp3")


def test_member_memory_fraction_shrinks_with_model_degree_only():
    f1 = member_memory_fraction(Parallelism())
    f2 = member_memory_fraction(TP2)
    f4 = member_memory_fraction(TP2PP2)
    assert f1 == 1.0 and 1.0 > f2 > f4 > 0.15
    # data parallelism replicates the model: no memory relief
    assert member_memory_fraction(Parallelism(data=8)) == 1.0


def test_member_name_roundtrip_and_rank_layout():
    assert member_name("job", 3) == "job#r3"
    assert gang_of_member("job#r3") == "job"
    assert gang_of_member("plain-job") == "plain-job"
    # tensor fastest-varying: ranks 0,1 share a TP group under tp2.pp2
    assert rank_coords(TP2PP2, 1) == (1, 0, 0)
    assert rank_coords(TP2PP2, 2) == (0, 1, 0)
    groups = axis_rank_groups(TP2PP2)
    assert groups["tensor"] == [(0, 1), (2, 3)]
    assert groups["pipeline"] == [(0, 2), (1, 3)]
    assert "data" not in groups  # degree-1 axes carry no traffic
    assert gang_world_size(gang_train("g", "stablelm-12b", TP2)) == 2
    assert is_gang(gang_train("g", "stablelm-12b", TP2))
    assert not is_gang(JobSpec("s", "granite-3-2b", SIM_SUITE))


# -- comms cost model --------------------------------------------------------------


def test_colocated_overhead_strictly_below_scattered():
    colocated = comm_overhead_s(TP2, {0: "d0", 1: "d0"}, 1e-3)
    scattered = comm_overhead_s(TP2, {0: "d0", 1: "d1"}, 1e-3)
    assert 0.0 < colocated < scattered
    # the gap is the bandwidth ratio plus the hop latency — exactly
    expected = colocated / DEFAULT_LINK.cross_bandwidth_frac + DEFAULT_LINK.cross_latency_s
    assert scattered == pytest.approx(expected)


def test_latency_term_breaks_ties_for_pure_compute_gangs():
    # zero collective bytes: a scattered ring still pays per-hop latency
    assert comm_overhead_s(TP2, {0: "d0", 1: "d0"}, 0.0) == 0.0
    assert comm_overhead_s(TP2, {0: "d0", 1: "d1"}, 0.0) == pytest.approx(
        DEFAULT_LINK.cross_latency_s
    )


def test_world_size_one_gang_has_zero_comm_overhead():
    # the degenerate edge runtime/ring.py also honours (a 1-ring is a no-op)
    assert comm_overhead_s(Parallelism(), {0: "d0"}, 1e-3) == 0.0
    assert gang_step_s([0.01], Parallelism(), {0: "d0"}, 1e-3) == 0.01


def test_ring_links_edge_shapes():
    assert ring_links([0]) == ()
    assert ring_links([0, 1]) == ((0, 1),)  # two members: one link, no ring
    assert ring_links([0, 1, 2]) == ((0, 1), (1, 2), (2, 0))  # odd ring closes


def test_gang_step_is_slowest_member_plus_overhead():
    step = gang_step_s([0.01, 0.03], TP2, {0: "d0", 1: "d0"}, 1e-3)
    assert step == pytest.approx(0.03 + comm_overhead_s(TP2, {0: "d0", 1: "d0"}, 1e-3))
    assert placement_spread({0: "d0", 1: "d0", 2: "d1"}) == 2


def test_link_model_validation():
    with pytest.raises(ValueError):
        LinkModel(cross_bandwidth_frac=0.0)
    with pytest.raises(ValueError):
        LinkModel(cross_latency_s=-1.0)


# -- placement search --------------------------------------------------------------


def test_split_counts_pack_vs_scatter():
    caps = [2, 3, 1]
    assert split_counts(caps, 4, "colocate") == [(1, 3), (0, 1)]  # fewest devices
    # round-robin: one per device first, the remainder to the earliest
    # device with spare capacity — maximum spread, fleet-order ties
    assert split_counts(caps, 4, "scatter") == [(0, 2), (1, 1), (2, 1)]
    assert split_counts(caps, 7, "colocate") is None  # capacity short: no partial
    assert split_counts([2, 2], 2, "colocate") == [(0, 2)]  # fleet-order tie-break


def test_plan_gang_all_or_nothing_and_preference():
    def probe(dev_idx, ranks):
        return [(f"slot{dev_idx}.{r}", 0.01) for r in ranks]

    pack = plan_gang(TP2, ["d0", "d1"], [2, 2], probe, 1e-3)
    assert pack is not None and pack.spread == 1 and pack.devices == ("d0", "d0")
    spread = plan_gang(TP2, ["d0", "d1"], [2, 2], probe, 1e-3, prefer="scatter")
    assert spread is not None and spread.spread == 2
    assert pack.step_s < spread.step_s  # comms price the scatter
    assert plan_gang(TP2PP2, ["d0"], [2], probe, 1e-3) is None  # no partial gang
    with pytest.raises(ValueError):
        plan_gang(TP2, ["d0"], [2], probe, 1e-3, prefer="best")


# -- cluster integration: admission ------------------------------------------------


def test_gang_admission_is_all_or_nothing():
    # one 80GB device hosts only 2 qwen2 tp2.pp2 members — a world_size-4
    # gang is rejected outright, never partially placed
    c = Cluster(_DBS, fleet(1))
    c.submit(gang_train("g", "qwen2-72b", TP2PP2), 0.0, epochs=1,
             samples_per_epoch=SIM_SAMPLES_PER_EPOCH)
    rep = c.run()
    row = rep.jobs[0]
    assert rep.rejected == 1 and "gang unplaceable" in row["rejected_reason"]
    # two 80GB devices: the same gang spans both, two members each
    c2 = Cluster(_DBS, fleet(2))
    cj = c2.submit(gang_train("g", "qwen2-72b", TP2PP2), 0.0, epochs=1,
                   samples_per_epoch=SIM_SAMPLES_PER_EPOCH)
    while c2.events and not cj.member_devices:
        c2.tick()
    assert cj.member_devices == ("d0", "d0", "d1", "d1")  # 2 members/device
    rep2 = c2.run()
    row2 = rep2.jobs[0]
    assert rep2.completed == 1
    assert row2["world_size"] == 4 and row2["parallelism"] == "tp2.pp2.dp1"
    assert row2["gang_spread"] == 2 and row2["gang_requeues"] == 0


def test_gang_row_keys_absent_for_singletons():
    c = Cluster(_DBS, fleet(1))
    c.submit(JobSpec("s", "granite-3-2b", SIM_SUITE), 0.0, epochs=1,
             samples_per_epoch=SIM_SAMPLES_PER_EPOCH)
    row = c.run().jobs[0]
    # the artifact-schema compatibility contract: gang keys only on gangs
    assert "world_size" not in row and "gang_spread" not in row


def test_shared_mode_fleet_rejects_gangs():
    # gangs are MIG-only: member isolation is what makes the lockstep step
    # predictable — an MPS fleet has zero gang capacity by definition
    c = Cluster(_DBS, fleet(2, mode=CollocationMode.MPS))
    c.submit(gang_train("g", "stablelm-12b", TP2), 0.0, epochs=1,
             samples_per_epoch=SIM_SAMPLES_PER_EPOCH)
    rep = c.run()
    assert rep.rejected == 1 and rep.still_queued == 0


def test_colocated_gang_strictly_beats_scattered():
    """The tentpole inequality at cluster level: identical gang, identical
    fleet; only the placement preference differs."""
    results = {}
    for prefer in ("colocate", "scatter"):
        c = Cluster(_DBS, fleet(4), gang_placement=prefer)
        c.submit(gang_train("g", "qwen2-72b", TP2PP2), 0.0, epochs=3,
                 samples_per_epoch=SIM_SAMPLES_PER_EPOCH)
        rep = c.run()
        assert rep.completed == 1
        results[prefer] = (rep.jobs[0]["jct_s"], rep.goodput_steps_per_s,
                           rep.jobs[0]["gang_spread"])
    assert results["colocate"][2] < results["scatter"][2]  # fewer devices
    assert results["colocate"][0] < results["scatter"][0]  # faster
    assert results["colocate"][1] > results["scatter"][1]  # more goodput


# -- cluster integration: failure semantics ----------------------------------------


def test_member_failure_requeues_the_whole_gang():
    c = Cluster(_DBS, fleet(2))
    cj = c.submit(gang_train("g", "qwen2-72b", TP2PP2), 0.0, epochs=1,
                  samples_per_epoch=SIM_SAMPLES_PER_EPOCH)
    c.inject_failure("d0", (0,), 1.0)  # hits member r0's slice only
    c.inject_repair("d0", (0,), 2.0)
    rep = c.run()
    row = rep.jobs[0]
    # one member's slice failed; the re-queue is gang-wide — both of d0's
    # members are in the kill set and d1's members did not keep running
    fail = [e for e in rep.failure_events if e["device"] == "d0"][0]
    assert set(fail["killed"]) >= {"g#r0", "g#r1"}
    assert row["gang_requeues"] == 1 and cj.gang_requeues == 1
    assert rep.completed == 1 and row["finished_s"] > 1.0
    assert rep.lost_steps > 0.0  # checkpoint rollback charged


def test_split_by_failure_never_orphans_gang_siblings():
    """Satellite regression at the elastic layer: a failure that hits one
    member's span kills the same-device sibling too (no orphaned member
    keeps running), while unrelated singletons survive untouched."""
    from repro.core.collocation import Assignment
    from repro.core.profiles import Placement

    r0 = dataclasses.replace(
        JobSpec("g#r0", "stablelm-12b", SIM_SUITE), gang="g")
    r1 = dataclasses.replace(
        JobSpec("g#r1", "stablelm-12b", SIM_SUITE), gang="g")
    solo = JobSpec("solo", "granite-3-2b", SIM_SUITE)
    assignments = [
        Assignment(r0, Placement("1g.5gb", 0), 0.01),
        Assignment(r1, Placement("1g.5gb", 1), 0.01),
        Assignment(solo, Placement("1g.5gb", 2), 0.01),
    ]
    killed, survivors = split_by_failure(assignments, {0})
    assert sorted(j.name for j in killed) == ["g#r0", "g#r1"]
    assert all(j.priority > 0 for j in killed)  # re-queue priority bump
    assert [a.job.name for a in survivors] == ["solo"]
    # no gang in the blast radius: singleton semantics unchanged
    killed2, survivors2 = split_by_failure(assignments, {2})
    assert [j.name for j in killed2] == ["solo"]
    assert sorted(a.job.name for a in survivors2) == ["g#r0", "g#r1"]


# -- re-timing equivalence + scenario ----------------------------------------------


def test_gang_trace_full_and_incremental_engines_agree():
    reports = []
    for retime in ("full", "incremental"):
        c = Cluster(_DBS, fleet(4), retime=retime, gang_reserve_after_s=0.5)
        for t, spec, epochs in make_trace("gang_pipeline", 0, 30, 4):
            c.submit(spec, t, epochs=epochs,
                     samples_per_epoch=SIM_SAMPLES_PER_EPOCH)
        reports.append(c.run().to_dict())
    assert reports[0] == reports[1]


def test_gang_pipeline_scenario_colocated_beats_scattered_goodput():
    """The scenario-level acceptance inequality (also gated in CI): same
    seed-0 trace, same all-MIG gang fleet — co-located goodput strictly
    beats scattered, and the full-slice-only baseline rejects every
    only-fits-as-a-gang job instead of running it."""
    cells = {
        p: run_cell("gang_pipeline", "all-mig", seed=0, gang_placement=p)
        for p in ("colocate", "scatter")
    }
    sums = {p: summarize_cell(c) for p, c in cells.items()}
    for s in sums.values():
        assert s["still_queued"] == 0 and s["completed"] == s["n_jobs"]
    assert (sums["colocate"]["goodput_steps_per_s"]
            > sums["scatter"]["goodput_steps_per_s"])
    assert sums["colocate"]["mean_jct_s"] < sums["scatter"]["mean_jct_s"]

    def mean_spread(cell):
        gangs = [j for j in cell["report"]["jobs"] if j.get("world_size", 1) > 1]
        assert gangs
        return sum(j["gang_spread"] for j in gangs) / len(gangs)

    assert mean_spread(cells["colocate"]) < mean_spread(cells["scatter"])

    degraded = summarize_cell(
        run_cell("gang_pipeline", "all-mig", seed=0, gang_degrade=True)
    )
    n_gangs = sum(
        1 for _, spec, _ in make_trace("gang_pipeline", 0, 60, 4)
        if getattr(spec, "world_size", 1) > 1 and spec.arch == "qwen2-72b"
    )
    assert n_gangs > 0 and degraded["rejected"] == n_gangs


def test_gang_pipeline_drains_on_every_policy():
    from repro.launch.simulate import POLICIES

    for policy in POLICIES:
        s = summarize_cell(run_cell("gang_pipeline", policy, seed=0, n_jobs=30))
        assert s["still_queued"] == 0, (policy, s)
        assert s["completed"] + s["rejected"] == s["n_jobs"], (policy, s)


# -- cluster integration: phase transitions ----------------------------------------


def test_gang_phase_transition_reprices_every_member():
    """A gang member crossing its warmup boundary re-prices ALL members at
    the new demand and re-derives the comm-priced gang step — placements
    stay put (F3 per member slice), only the pricing moves."""
    from repro.core.workload import member_demand

    c = Cluster(_DBS, fleet(2))
    cj = c.submit(gang_train("g", "qwen2-72b", TP2PP2), 0.0, epochs=1,
                  samples_per_epoch=SIM_SAMPLES_PER_EPOCH)
    c.run_until(0.0)  # placed
    warm_step = cj.step_s
    placements = {
        r: c.devices[d].assignments[member_name("g", r)].placement
        for r, d in enumerate(cj.member_devices)
    }
    while cj.phase_transitions == 0 and c.events:
        c.tick()
    assert cj.phase_transitions == 1  # warmup -> steady
    mdemand = member_demand(cj.spec, cj.active_demand())
    member_steps = []
    for rank, dname in enumerate(cj.member_devices):
        d = c.devices[dname]
        a = d.assignments[member_name("g", rank)]
        assert a.placement == placements[rank]  # no member moved
        assert a.predicted_step_s == pytest.approx(
            d.scheduler.predict_step(a.job, a.profile, mdemand)
        )
        member_steps.append(a.predicted_step_s)
    # the gang step is the slowest member plus non-negative comm overhead,
    # and the steady re-price actually changed the warmup-era step
    assert cj.step_s >= max(member_steps)
    assert cj.step_s != warm_step
    rep = c.run()
    assert rep.completed == 1
    assert rep.jobs[0]["phase_transitions"] >= 2  # ... -> checkpoint too


def test_gang_phase_transitions_identical_on_both_retime_engines():
    """PHASE_TRANSITION x gangs across the engine seam: phase-aware gangs
    (wide and narrow) plus singleton filler must re-time to identical
    reports under retime="full" and retime="incremental" — and the trace
    must actually cross phase boundaries for the comparison to bite."""
    reports = []
    for retime in ("full", "incremental"):
        c = Cluster(_DBS, fleet(4), retime=retime, gang_reserve_after_s=0.5)
        c.submit(gang_train("g4", "qwen2-72b", TP2PP2), 0.0, epochs=1,
                 samples_per_epoch=SIM_SAMPLES_PER_EPOCH)
        c.submit(gang_train("g2", "stablelm-12b", TP2), 0.01, epochs=2,
                 samples_per_epoch=SIM_SAMPLES_PER_EPOCH)
        c.submit(JobSpec("solo", "granite-3-2b", SIM_SUITE), 0.02, epochs=1,
                 samples_per_epoch=SIM_SAMPLES_PER_EPOCH)
        reports.append(c.run().to_dict())
    assert reports[0] == reports[1]
    gang_rows = [j for j in reports[0]["jobs"] if j.get("world_size", 1) > 1]
    assert gang_rows and all(j["phase_transitions"] >= 2 for j in gang_rows)


# -- CLI surfacing -----------------------------------------------------------------


def test_cli_list_surfaces_gang_scenario_and_parameters(capsys):
    assert simulate_main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "gang_pipeline" in out
    assert "colocate, scatter" in out
    for name in PARALLELISMS:
        assert name in out
    assert "world_size 4" in out  # derived world sizes are printed


def test_cli_unknown_gang_parallelism_errors_with_choices(capsys):
    with pytest.raises(SystemExit) as e:
        simulate_main(["--gang-parallelism", "tp3"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "tp2.pp2" in err and "invalid choice" in err


def test_cli_unknown_gang_world_size_errors_with_choices(capsys):
    with pytest.raises(SystemExit) as e:
        simulate_main(["--gang-world-size", "3"])
    assert e.value.code == 2
    # Python 3.12 quotes the rejected value: invalid choice: '3'
    assert "invalid choice: '3'" in capsys.readouterr().err


def test_cli_mismatched_world_size_lists_registered_descriptors(capsys):
    # 4 is a legal world size, but not tp2's — the error names every
    # registered descriptor with its derived world size
    with pytest.raises(SystemExit) as e:
        simulate_main(["--gang-world-size", "4", "--gang-parallelism", "tp2"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "world_size is derived" in err and "tp2.pp2=4" in err
