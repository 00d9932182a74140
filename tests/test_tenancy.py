"""Multi-tenant serving: per-tenant SLOs over one shared expert pool.

Covers the tenancy seam end to end:

* the per-ACCOUNT concurrency-limit fix in the event simulator (two
  accounts at ``concurrency_limit=1`` run concurrently; one account
  still serializes; the zero-fault path stays bit-identical),
* ``TenantAccounting`` conservation — per-tenant billed cost / fault
  counters sum float-exactly to the fleet totals,
* cache residency quotas (ownership capped, residency HITS shared),
* the fair-share + priority slot scheduler (FIFO bit-identity without
  tenants, deficit fairness / aging / priority / weights with),
* ``_merge_reports``'s sequential-vs-wall-clock throughput contract and
  the per-tenant block merge,
* the ``_plan_fn_extra_kw`` sniffing fix (``functools.partial`` pinned
  keywords never clobbered, ``**kwargs`` accepted, unsniffable C
  callables degrade to no forwarding),
* the ``ods-tenant`` planner registry entry + consolidation metadata,
* the headline: one shared plan beats N independent fleets on billed
  GB-seconds while the latency-bound tenant's p99 holds.
"""
import functools
import json
import types
from pathlib import Path

import numpy as np
import pytest

from repro.core.costmodel import ModelProfile, PlatformSpec
from repro.core.simulator import (FaultProfile, ServerlessSimulator,
                                  TenantAccounting, replica_accounts,
                                  split_replicas)
from repro.plan.backends import (_merge_reports, _plan_fn_extra_kw,
                                 run_plan_over_trace)
from repro.plan.incremental import IncrementalODSPlanner
from repro.plan.planner import get_planner
from repro.plan.tenancy import (MultiTenantPlanner,
                                run_tenants_independently,
                                run_tenants_over_traces)
from repro.serving.scheduler import SlotScheduler
from repro.traces import Tenant, TenantSLO, align_tenant_windows, \
    mixed_tenant_pair

GOLDEN_DIR = Path(__file__).parent / "golden"

SPEC = PlatformSpec()
PROF = ModelProfile(
    num_moe_layers=4, experts_per_layer=8,
    expert_param_bytes=28e6, token_in_bytes=3072.0, token_out_bytes=3072.0,
    u_ref_s=2e-4, intermediate_bytes=4e6, nonmoe_param_bytes=9e6)


def _demand(L=4, E=8, seed=0, scale=2000):
    rng = np.random.default_rng(seed)
    zipf = (1.0 / np.arange(1, E + 1)) ** 1.2
    d = scale * zipf / zipf.sum() * E
    return np.stack([rng.permutation(d) for _ in range(L)])


@pytest.fixture(scope="module")
def plan():
    return get_planner("ods").plan(_demand(), PROF, SPEC, t_limit_s=1e9)


REAL = _demand(seed=3, scale=2400)
N_TOK = int(REAL.sum())


# ---------------------------------------------------------------------------
# Bugfix: per-ACCOUNT concurrency limit (was one global heap)
# ---------------------------------------------------------------------------

class TestPerAccountConcurrency:
    FAULTS = FaultProfile(concurrency_limit=1)

    def _run(self, plan, tenants=None):
        sim = ServerlessSimulator(PROF, SPEC, seed=7, faults=self.FAULTS)
        return sim.run(plan, REAL, N_TOK, tenants=tenants)

    @staticmethod
    def _disjoint_split():
        """Each tenant hot on its own experts (even vs odd): the
        replica apportionment then assigns each expert's replicas to
        the tenant that routes to it, so the two accounts genuinely
        share the layer wave. (A 50/50 proportional split of EVERY
        expert would tie-break all single replicas to account 0 and
        degenerate to the one-account schedule.)"""
        mask = np.zeros_like(REAL)
        mask[:, ::2] = 1.0
        return REAL * mask, REAL * (1.0 - mask)

    def test_two_accounts_run_concurrently(self, plan):
        """Two accounts at limit=1 must NOT queue behind each other:
        the fleet-wide queue delay and latency strictly drop vs the
        same demand under one account (the old single-heap bug made
        them identical)."""
        solo = self._run(plan)
        da, db = self._disjoint_split()
        two = self._run(plan, tenants=[("a", da), ("b", db)])
        assert solo.queue_delay_s > 0.0          # the limit binds
        assert two.queue_delay_s < solo.queue_delay_s
        assert two.latency_s < solo.latency_s

    def test_one_account_still_serializes(self, plan):
        """Within the two-account run each account's OWN invocations
        still queue behind its limit."""
        da, db = self._disjoint_split()
        two = self._run(plan, tenants=[("a", da), ("b", db)])
        assert set(two.tenants) == {"a", "b"}
        for name, blk in two.tenants.items():
            assert blk["queue_delay_s"] > 0.0, name

    def test_single_account_split_is_bit_identical(self, plan):
        """One named tenant owning ALL demand replays the historical
        single-heap schedule exactly."""
        solo = self._run(plan)
        one = self._run(plan, tenants=[("solo", REAL, N_TOK)])
        assert one.queue_delay_s == solo.queue_delay_s
        assert one.latency_s == solo.latency_s
        assert one.billed_cost == solo.billed_cost
        assert one.cold_starts == solo.cold_starts

    def test_zero_fault_path_bit_identical(self, plan):
        """No faults: a tenant split must not perturb ANY global field
        — the tenant-less wire dict equals the tenant run's dict minus
        its conditional "tenants" block."""
        base = ServerlessSimulator(PROF, SPEC, seed=7).run(
            plan, REAL, N_TOK)
        ten = ServerlessSimulator(PROF, SPEC, seed=7).run(
            plan, REAL, N_TOK,
            tenants={"a": REAL * 0.25, "b": REAL * 0.75})
        db, dt = base.to_dict(), ten.to_dict()
        assert "tenants" not in db, \
            "tenant-less reports must keep the historical wire schema"
        assert set(dt) - set(db) == {"tenants"}
        dt.pop("tenants")
        assert db == dt


# ---------------------------------------------------------------------------
# TenantAccounting conservation
# ---------------------------------------------------------------------------

HEAVY = FaultProfile(cold_start_prob=0.5, warm_pool=2, straggler_prob=0.1,
                     failure_prob=0.1, concurrency_limit=8)


class TestConservation:
    def _tenant_run(self, plan):
        sim = ServerlessSimulator(PROF, SPEC, seed=7, faults=HEAVY)
        return sim.run(plan, REAL, N_TOK,
                       tenants=[("big", REAL * 0.6, 0.6 * N_TOK),
                                ("small", REAL * 0.4, 0.4 * N_TOK)])

    def test_costs_and_counters_sum_to_fleet_totals(self, plan):
        rep = self._tenant_run(plan)
        blocks = rep.tenants.values()
        np.testing.assert_allclose(
            sum(b["billed_cost"] for b in blocks), rep.billed_cost,
            rtol=1e-9, err_msg="tenant billed costs must conserve")
        assert sum(b["num_tokens"] for b in blocks) == rep.num_tokens
        for key, tot in (("cold_starts", rep.cold_starts),
                         ("retries", rep.retries),
                         ("stragglers", rep.stragglers)):
            assert sum(b[key] for b in blocks) == tot, key
        np.testing.assert_allclose(
            sum(b["cold_start_s"] for b in blocks), rep.cold_start_s,
            rtol=1e-9)
        np.testing.assert_allclose(
            sum(b["queue_delay_s"] for b in blocks), rep.queue_delay_s,
            rtol=1e-9)

    def test_tenant_latency_bounded_by_fleet_latency(self, plan):
        rep = self._tenant_run(plan)
        for name, blk in rep.tenants.items():
            assert blk["latency_s"] <= rep.latency_s + 1e-12, name
            assert blk["latency_s"] > 0.0, name

    def test_normalize_tenants_validation(self, plan):
        sim = ServerlessSimulator(PROF, SPEC, seed=7)
        with pytest.raises(ValueError, match="shape"):
            sim.run(plan, REAL, N_TOK,
                    tenants=[("a", REAL[:, :4])])
        with pytest.raises(ValueError):
            sim.run(plan, REAL, N_TOK,
                    tenants=[("a", REAL * 0.5), ("b", REAL * 0.3)])
        with pytest.raises(ValueError, match="duplicate"):
            sim.run(plan, REAL, N_TOK,
                    tenants=[("a", REAL * 0.5), ("a", REAL * 0.5)])


# ---------------------------------------------------------------------------
# Distributed gateway: per-account queue-delay / makespan attribution
# ---------------------------------------------------------------------------


class TestDistributedAttribution:
    """Bugfix: the distributed gateway split the dispatcher's wave-global
    queue delay across tenants by TOKEN SHARE and folded the wave's
    makespan excess into EVERY tenant's latency. Both now attribute to
    the account whose invocation incurred them (the dispatcher reports
    per-invocation queue waits and spans), mirroring the simulator's
    ``wave_tallies`` contract — and conservation still holds."""

    def _run(self, tenants, plan):
        from repro.dist.backend import DistributedBackend
        with DistributedBackend(PROF, SPEC, faults=HEAVY, seed=11,
                                transport="inline",
                                verify_outputs=False) as be:
            return be.run(plan, REAL, N_TOK, tenants=tenants)

    def test_conservation_under_per_account_attribution(self, plan):
        mask = np.zeros_like(REAL)
        mask[:, ::2] = 1.0
        rep = self._run([("a", REAL * mask), ("b", REAL * (1.0 - mask))],
                        plan)
        blocks = rep.tenants.values()
        np.testing.assert_allclose(
            sum(b["billed_cost"] for b in blocks), rep.billed_cost,
            rtol=1e-9, err_msg="tenant billed costs must conserve")
        np.testing.assert_allclose(
            sum(b["queue_delay_s"] for b in blocks), rep.queue_delay_s,
            rtol=1e-9,
            err_msg="per-account queue delay must sum to the fleet total")
        for key, tot in (("cold_starts", rep.cold_starts),
                         ("retries", rep.retries),
                         ("stragglers", rep.stragglers)):
            assert sum(b[key] for b in blocks) == tot, key
        # each tenant carries the shared critical path plus only its OWN
        # makespan excess, so nobody exceeds the fleet latency
        for name, blk in rep.tenants.items():
            assert blk["latency_s"] <= rep.latency_s + 1e-9, name

    def test_unattributed_tenant_pays_nothing(self, plan):
        """A tenant with zero demand owns no invocations: it must see
        ZERO queue delay (the old token-share split handed it nearly
        half) and none of the fault-driven makespan excess (the old
        code put the global excess in every tenant's latency)."""
        rep = self._run([("owner", REAL, 0.55 * N_TOK),
                         ("idle", np.zeros_like(REAL), 0.45 * N_TOK)],
                        plan)
        owner, idle = rep.tenants["owner"], rep.tenants["idle"]
        assert idle["queue_delay_s"] == 0.0
        np.testing.assert_allclose(owner["queue_delay_s"],
                                   rep.queue_delay_s, rtol=1e-9)
        # the owner holds every invocation, so its makespan IS the
        # wave's: owner latency reconstructs the fleet latency
        assert owner["latency_s"] == pytest.approx(rep.latency_s,
                                                   rel=1e-9)
        # the heavy fault profile produced real wave excess; only the
        # owner carries it
        assert rep.retries + rep.stragglers + rep.cold_starts > 0
        assert idle["latency_s"] < owner["latency_s"]
        assert idle["billed_cost"] == pytest.approx(0.0, abs=1e-12)


# ---------------------------------------------------------------------------
# Replica apportionment
# ---------------------------------------------------------------------------

def test_split_replicas_largest_remainder():
    out = split_replicas(7, np.array([0.5, 0.3, 0.2]))
    assert out.sum() == 7 and out.tolist() == [4, 2, 1]
    # deterministic tie-break toward the lower index
    assert split_replicas(3, np.array([0.5, 0.5])).tolist() == [2, 1]
    assert split_replicas(0, np.array([1.0])).tolist() == [0]


def test_replica_accounts_groups_by_account():
    g = np.array([3, 2, 0, 1])
    dem = np.array([[6.0, 0.0, 0.0, 1.0],
                    [3.0, 5.0, 0.0, 0.0]])
    out = replica_accounts(g, dem)
    assert [a.tolist() for a in out] == [[0, 0, 1], [1, 1], [], [0]]
    for gi, a in zip(g, out):
        assert len(a) == gi
        assert (np.diff(a) >= 0).all()   # ascending account order


# ---------------------------------------------------------------------------
# Cache residency quotas
# ---------------------------------------------------------------------------

class TestCacheQuotas:
    def _model(self, plan):
        from repro.expcache import CacheConfig, ContainerCacheModel
        return ContainerCacheModel.from_plan(
            plan, PROF, SPEC, config=CacheConfig(policy="lru"))

    def test_quota_caps_ownership_and_counts_denials(self, plan):
        m = self._model(plan)
        m.set_tenant_quotas({"a": 0.01, "b": 1.0})   # cap(a) == 1
        c = m._admit(0, 0, tenant="a")
        assert c is not None and c.tenant == "a"
        c.used = True                   # a's only container is busy
        denials0 = m.stats["quota_denials"]
        assert m._admit(0, 1, tenant="a") is None
        assert m.stats["quota_denials"] == denials0 + 1
        # the other tenant is untouched by a's cap
        cb = m._admit(0, 1, tenant="b")
        assert cb is not None and cb.tenant == "b"

    def test_residency_hits_stay_shared_across_tenants(self, plan):
        m = self._model(plan)
        m.set_tenant_quotas({"a": 0.5, "b": 0.5})
        owner = m._admit(0, 0, tenant="a")
        assert owner is not None
        wave = m.wave(0, FaultProfile())
        state = types.SimpleNamespace(pre_left=None, warm_left=0)
        acc = wave.access(0, np.random.default_rng(0), state, tenant="b")
        assert acc.kind == "hit" and not acc.cold, \
            "quotas bound ownership, not reads: b must hit a's resident"

    def test_quota_validation_and_disable(self, plan):
        m = self._model(plan)
        with pytest.raises(ValueError):
            m.set_tenant_quotas({"a": 0.0})
        with pytest.raises(ValueError):
            m.set_tenant_quotas({"a": 1.5})
        m.set_tenant_quotas({"a": 0.5})
        m.set_tenant_quotas(None)
        assert m.tenant_quotas == {}


# ---------------------------------------------------------------------------
# Fair-share + priority slot scheduler
# ---------------------------------------------------------------------------

class TestFairShareScheduler:
    def _drain(self, sched, n, step0=0):
        """Admit n requests one per step from a single slot; return the
        admitted tenant order."""
        order = []
        for k in range(n):
            req = sched.admit_next(0, step0 + k)
            assert req is not None
            order.append(req.tenant)
            sched.finish(req, "length")
        return order

    def test_tenantless_queue_is_pure_fifo(self):
        s = SlotScheduler(1)
        uids = [s.submit(np.arange(4), max_new_tokens=4).uid
                for _ in range(5)]
        got = []
        for k in range(5):
            r = s.admit_next(0, k)
            got.append(r.uid)
            s.finish(r, "length")
        assert got == uids, "no tenants => historical FIFO order"
        assert s.fairness_stats() == {}, \
            "FIFO path must not touch the fair-share accounts"

    def test_deficit_round_robin_interleaves_tenants(self):
        s = SlotScheduler(1, aging=0.0)
        for _ in range(3):
            s.submit(np.arange(8), max_new_tokens=8, tenant="a")
        for _ in range(3):
            s.submit(np.arange(8), max_new_tokens=8, tenant="b")
        assert self._drain(s, 6) == ["a", "b", "a", "b", "a", "b"], \
            "equal-cost tenants must alternate, not drain a's backlog"

    def test_aging_lets_backlogged_tenant_overtake(self):
        # b's request sits while a is served; with aging on, b's wait
        # eventually beats a's lower served-token account
        s = SlotScheduler(1, aging=4.0)
        for _ in range(4):
            s.submit(np.arange(8), max_new_tokens=8, tenant="a",
                     submit_step=0)
        s.submit(np.arange(8), max_new_tokens=8, tenant="b",
                 submit_step=0)
        order = self._drain(s, 5)
        assert order.index("b") < len(order) - 1, \
            "aging must pull the waiting tenant forward"
        # starvation bound: with aging off b would still win round-robin
        s0 = SlotScheduler(1, aging=0.0)
        s0.submit(np.arange(800), max_new_tokens=8, tenant="a")
        s0.submit(np.arange(8), max_new_tokens=8, tenant="b")
        s0.submit(np.arange(8), max_new_tokens=8, tenant="a")
        assert self._drain(s0, 3) == ["a", "b", "a"]

    def test_priority_admits_first_and_priority_aging_unstarves(self):
        s = SlotScheduler(1, aging=0.0, priority_aging=0.0)
        s.submit(np.arange(8), max_new_tokens=8, tenant="lo", priority=0)
        s.submit(np.arange(8), max_new_tokens=8, tenant="hi", priority=1)
        assert self._drain(s, 2) == ["hi", "lo"]
        # priority_aging > 0: a long-waiting low-priority request beats
        # a fresh high-priority one (starvation freedom)
        s = SlotScheduler(1, aging=0.0, priority_aging=0.5)
        s.submit(np.arange(8), max_new_tokens=8, tenant="lo", priority=0,
                 submit_step=0)
        s.submit(np.arange(8), max_new_tokens=8, tenant="hi", priority=1,
                 submit_step=10)
        req = s.admit_next(0, step=13)   # lo waited 13, hi waited 3
        assert req.tenant == "lo"

    def test_weights_scale_fair_share(self):
        s = SlotScheduler(1, aging=0.0, weights={"a": 2.0, "b": 1.0})
        for _ in range(6):
            s.submit(np.arange(8), max_new_tokens=8, tenant="a")
            s.submit(np.arange(8), max_new_tokens=8, tenant="b")
        order = self._drain(s, 9)
        assert order.count("a") == 6 and order.count("b") == 3, \
            "weight 2 tenant gets twice the admitted tokens"

    def test_knob_validation(self):
        with pytest.raises(ValueError):
            SlotScheduler(1, aging=-1.0)
        with pytest.raises(ValueError):
            SlotScheduler(1, weights={"a": 0.0})


# ---------------------------------------------------------------------------
# Report merging: sequential vs concurrent wall clock, tenant blocks
# ---------------------------------------------------------------------------

class TestMergeReports:
    def _reports(self, plan):
        sim = ServerlessSimulator(PROF, SPEC, seed=7, faults=HEAVY)
        r1 = sim.run(plan, REAL, N_TOK,
                     tenants=[("a", REAL * 0.5), ("b", REAL * 0.5)])
        r2 = sim.run(plan, REAL * 1.1, int(1.1 * N_TOK),
                     tenants=[("a", REAL * 0.55), ("b", REAL * 0.55)])
        return [r1, r2]

    def test_sequential_merge_keeps_historical_throughput(self, plan):
        reps = self._reports(plan)
        merged = _merge_reports(reps, backend="simulator")
        total_lat = sum(r.latency_s for r in reps)
        n_tok = sum(r.num_tokens for r in reps)
        assert merged.throughput_tps == pytest.approx(
            n_tok / total_lat, rel=1e-12), \
            "no override => tokens / SUM(latency), the pinned convention"
        assert "wall_clock_s" not in merged.extras

    def test_wall_clock_override_reports_concurrent_throughput(self, plan):
        reps = self._reports(plan)
        wall = max(r.latency_s for r in reps)
        merged = _merge_reports(reps, backend="simulator",
                                wall_clock_s=wall)
        n_tok = sum(r.num_tokens for r in reps)
        assert merged.throughput_tps == pytest.approx(
            n_tok / wall, rel=1e-12)
        assert merged.extras["wall_clock_s"] == wall
        # latency_s stays the billed SERIAL sum either way
        assert merged.latency_s == pytest.approx(
            sum(r.latency_s for r in reps), rel=1e-12)

    def test_tenant_blocks_merge_with_p99_samples(self, plan):
        reps = self._reports(plan)
        merged = _merge_reports(reps, backend="simulator")
        for name in ("a", "b"):
            blk = merged.tenants[name]
            samples = [r.tenants[name]["latency_s"] for r in reps]
            assert blk["latency_samples"] == pytest.approx(samples)
            assert blk["latency_s"] == pytest.approx(sum(samples))
            assert blk["p99_latency_s"] == pytest.approx(
                float(np.percentile(samples, 99.0)))
            assert blk["max_latency_s"] == pytest.approx(max(samples))
            assert blk["billed_cost"] == pytest.approx(
                sum(r.tenants[name]["billed_cost"] for r in reps))
        # re-merging a merged report must keep the ORIGINAL per-window
        # samples (p99 stays judged on windows, not on merged sums)
        again = _merge_reports([merged], backend="simulator")
        assert again.tenants["a"]["latency_samples"] == \
            merged.tenants["a"]["latency_samples"]


# ---------------------------------------------------------------------------
# Bugfix: _plan_fn_extra_kw vs functools.partial / **kwargs callables
# ---------------------------------------------------------------------------

class TestPlanFnSniffing:
    def test_partial_pinned_keyword_is_never_clobbered(self):
        seen = {}

        def base(demand, *, delta=None, budget_s=None):
            seen.update(delta=delta, budget_s=budget_s)

        fn = functools.partial(base, delta=0.2)
        kw = _plan_fn_extra_kw(fn, 0.05, 1.5)
        assert kw == {"budget_s": 1.5}, \
            "the caller pinned delta=0.2 on purpose; forwarding delta " \
            "again would raise or silently override it"
        fn(np.zeros((2, 2)), **kw)       # must not TypeError
        assert seen == {"delta": 0.2, "budget_s": 1.5}

    def test_partial_over_incremental_planner_forwards(self):
        pl = IncrementalODSPlanner(delta=0.5)
        fn = functools.partial(pl.plan, profile=PROF, platform=SPEC)
        kw = _plan_fn_extra_kw(fn, 0.05, None)
        assert kw == {"delta": 0.05}
        plan = fn(_demand(), **kw)
        assert plan.planner == pl.name

    def test_var_keyword_accepts_everything(self):
        kw = _plan_fn_extra_kw(lambda d, **kwargs: None, 0.1, 2.0)
        assert kw == {"delta": 0.1, "budget_s": 2.0}

    def test_plain_callable_gets_nothing(self):
        assert _plan_fn_extra_kw(lambda d: None, 0.1, 2.0) == {}

    def test_wrapped_decorator_is_unwrapped(self):
        def inner(d, *, delta=None):
            return None

        @functools.wraps(inner)
        def outer(*a, **k):
            return inner(*a, **k)

        assert _plan_fn_extra_kw(outer, 0.1, None) == {"delta": 0.1}

    def test_unsniffable_callable_degrades_to_empty(self):
        # max is a C builtin with several call forms, so it carries no
        # text signature and inspect.signature raises; the partial wrapper
        # used to make the sniff crash or mis-forward
        assert _plan_fn_extra_kw(functools.partial(max, 3),
                                 0.1, 1.0) == {}

    def test_no_request_no_sniff(self):
        assert _plan_fn_extra_kw(object(), None, None) == {}

    def test_end_to_end_partial_plan_fn_over_trace(self, plan):
        """run_plan_over_trace with a partial-wrapped incremental
        planner: the pinned delta must survive and the loop must not
        crash on duplicate keywords."""
        from repro.traces import bursty_arrivals, demand_trace, \
            zipf_popularity
        trace = demand_trace(bursty_arrivals(3.0, 4, seed=0),
                             zipf_popularity(4, 8, seed=0),
                             tokens_per_request=64)
        pl = IncrementalODSPlanner(delta=0.4)
        sim = ServerlessSimulator(PROF, SPEC, seed=7, faults=HEAVY)
        fn = functools.partial(pl.plan, profile=PROF, platform=SPEC,
                               delta=0.4)
        res = run_plan_over_trace(plan, trace, sim, PROF, SPEC,
                                  plan_fn=fn, delta=0.05)
        assert len(res["reports"]) == len(trace)
        assert pl.delta == 0.4


# ---------------------------------------------------------------------------
# Multi-tenant planner + trace loops
# ---------------------------------------------------------------------------

class TestMultiTenantPlanner:
    def _pair(self, steps=4):
        return list(mixed_tenant_pair(4, 8, steps=steps, seed=0))

    def test_registry_and_consolidation_metadata(self):
        tenants = self._pair()
        pl = get_planner("ods-tenant", tenants=tenants)
        assert isinstance(pl, MultiTenantPlanner)
        plan = pl.plan_shared(PROF, SPEC)
        meta = plan.metadata["tenants"]
        assert meta["names"] == ["bursty", "diurnal"]
        assert meta["t_limit_s"] == 60.0, \
            "joint limit = tightest latency-bound tenant's p99 target"
        assert meta["pooled_cost"] > 0.0
        assert meta["standalone_cost"] >= meta["pooled_cost"], \
            "pooling never costs more than the per-tenant fleets"
        assert meta["consolidation_savings"] == pytest.approx(
            meta["standalone_cost"] - meta["pooled_cost"])
        for q in meta["quotas"].values():
            assert pl.quota_floor <= q <= 1.0
        assert abs(sum(meta["shares"]) - 1.0) < 1e-9

    def test_planner_validation(self):
        with pytest.raises(ValueError, match="tenants"):
            MultiTenantPlanner([])
        t = self._pair()[0]
        with pytest.raises(ValueError, match="duplicate"):
            MultiTenantPlanner([t, t])
        with pytest.raises(ValueError, match="quota_floor"):
            MultiTenantPlanner(self._pair(), quota_floor=0.0)

    def test_align_tenant_windows_pads_short_traces(self):
        a, b = self._pair(steps=4)
        b.trace.windows = b.trace.windows[:2]
        rows = align_tenant_windows([a, b])
        assert len(rows) == 4 and all(len(r) == 2 for r in rows)
        assert rows[3][1].num_tokens == 0
        assert not rows[3][1].demand.any()

    def test_shared_run_attributes_every_tenant(self):
        tenants = self._pair()
        res = run_tenants_over_traces(
            tenants, PROF, SPEC, seed=0,
            faults=FaultProfile(cold_start_prob=0.3, warm_pool=1),
            cache="lru")
        merged = res["merged"]
        assert set(merged.tenants) == {"bursty", "diurnal"}
        total = sum(b["billed_cost"] for b in merged.tenants.values())
        assert total == pytest.approx(merged.billed_cost, rel=1e-9)
        assert len(res["reports"]) == len(tenants[0].trace)
        assert res["final_plan"].meets_slo

    def test_shared_beats_independent_within_slo(self):
        """The PR's acceptance headline at test scale: one pooled fleet
        bills fewer GB-seconds than two independent fleets, and the
        latency-bound tenant's p99 stays under its target."""
        tenants = self._pair(steps=6)
        faults = FaultProfile(cold_start_prob=0.3, warm_pool=1)
        shared = run_tenants_over_traces(tenants, PROF, SPEC, seed=0,
                                         faults=faults, cache="lru")
        indep = run_tenants_independently(tenants, PROF, SPEC, seed=0,
                                          faults=faults, cache="lru")
        s_cost = shared["merged"].billed_cost
        i_cost = indep["merged"].billed_cost
        assert s_cost < i_cost, \
            f"shared fleet must consolidate: {s_cost} >= {i_cost}"
        for t in tenants:
            if t.slo.kind != "latency":
                continue
            p99 = shared["merged"].tenants[t.name]["p99_latency_s"]
            assert p99 <= t.slo.p99_target_s, \
                f"{t.name} p99 {p99} blew its SLO {t.slo.p99_target_s}"


# ---------------------------------------------------------------------------
# Golden fixtures: the tenant wire block + pre-tenancy schema stability
# ---------------------------------------------------------------------------

def _make_tenant_report(plan) -> dict:
    sim = ServerlessSimulator(PROF, SPEC, seed=7, faults=HEAVY)
    rep = sim.run(plan, REAL, N_TOK,
                  tenants=[("bursty", REAL * 0.6, int(0.6 * N_TOK)),
                           ("diurnal", REAL * 0.4, int(0.4 * N_TOK))])
    return rep.to_dict()


def test_tenant_report_golden(plan, regen_golden):
    from test_golden_regression import _check_or_regen
    current = _make_tenant_report(plan)
    blk = current["tenants"]
    assert set(blk) == {"bursty", "diurnal"}
    for t in blk.values():
        assert t["billed_cost"] > 0.0 and t["latency_s"] > 0.0
    _check_or_regen("report_tenants.json", current, regen_golden)


@pytest.mark.parametrize("name", ["report_simulator.json",
                                  "report_faulted.json",
                                  "report_prewarmed.json"])
def test_committed_goldens_stay_tenant_free(name):
    """The conditional "tenants" block must NOT leak into the committed
    pre-tenancy fixtures (their absence IS the bit-identity contract)."""
    doc = json.loads((GOLDEN_DIR / name).read_text())
    assert "tenants" not in doc
