"""Multi-tenant QoS ring (docs/multi-tenancy.md): tenant identity,
weighted-fair queue math (DRR bounds), per-tenant bucket isolation,
tier-aware engine scheduling (batch preemption releases pages),
class-aware fleet state, canary-gossip convergence, tenant-scoped
fake-engine faults, and the in-process flood-isolation e2e — one tenant
offered 10x its admitted rate must not move another tenant's p99 by
more than 10%, on one router replica and on two gossiping replicas.
"""

import asyncio
import json
import socket
import time

import aiohttp
import pytest
from aiohttp import web

from production_stack_tpu.engine.kv_manager import BlockAllocator
from production_stack_tpu.engine.scheduler import Scheduler, SchedulerConfig
from production_stack_tpu.engine.sequence import SamplingParams, Sequence
from production_stack_tpu.resilience.admission import AdmissionController
from production_stack_tpu.resilience.tenancy import (
    TIER_BATCH,
    TIER_INTERACTIVE,
    TenantConfig,
    TenantSpec,
    WeightedFairQueue,
    tier_rank,
)
from production_stack_tpu.router.app import create_app
from production_stack_tpu.router.parser import parse_args
from production_stack_tpu.router.routing import scoring
from production_stack_tpu.router.state.gossip import GossipStateBackend
from production_stack_tpu.testing.fake_engine import create_fake_engine_app

from .router_utils import reset_router_singletons

MODEL = "fake/model"


@pytest.fixture(autouse=True)
def _reset():
    reset_router_singletons()
    yield
    reset_router_singletons()


# ---------------------------------------------------------------------------
# Identity derivation
# ---------------------------------------------------------------------------


def test_tenant_resolution_api_key_beats_header():
    cfg = TenantConfig({
        "acme": TenantSpec("acme", weight=4.0, api_keys=("sk-acme",)),
        "crawler": TenantSpec("crawler", tier=TIER_BATCH),
    })
    # API key: authenticated identity wins over self-declaration.
    spec = cfg.resolve({"X-PST-Tenant": "crawler"}, api_key="sk-acme")
    assert spec.name == "acme" and spec.weight == 4.0
    # Header honored when no key mapping matched.
    assert cfg.resolve({"X-PST-Tenant": "crawler"}).name == "crawler"
    assert cfg.resolve({"X-PST-Tenant": "crawler"}).tier == TIER_BATCH
    # Neither: the default tenant.
    assert cfg.resolve({}).name == "default"


def test_tenant_adhoc_names_bounded_and_defaulted():
    cfg = TenantConfig(default_weight=2.0, default_tier=TIER_BATCH)
    spec = cfg.resolve({"X-PST-Tenant": "newcomer"})
    assert spec.name == "newcomer"
    assert spec.weight == 2.0 and spec.tier == TIER_BATCH
    # A flood of unique names stays O(cap).
    from production_stack_tpu.resilience.tenancy import MAX_ADHOC_TENANTS

    for i in range(MAX_ADHOC_TENANTS + 100):
        cfg.resolve({"X-PST-Tenant": f"t{i}"})
    assert len(cfg._adhoc) <= MAX_ADHOC_TENANTS


def test_tenant_config_from_file(tmp_path):
    path = tmp_path / "tenants.json"
    path.write_text(json.dumps({
        "tenants": {
            "acme": {"weight": 3, "tier": "interactive",
                     "deadline_ms": 1500, "api_keys": ["k1"]},
            "crawler": {"weight": 1, "tier": "batch", "rate": 2.5},
        }
    }))
    cfg = TenantConfig.from_file(str(path))
    assert cfg.tenants["acme"].deadline_ms == 1500
    assert cfg.tenants["crawler"].rate == 2.5
    assert cfg.resolve({}, api_key="k1").name == "acme"
    # weight_sum covers configured tenants + the default share.
    assert cfg.weight_sum() == pytest.approx(3 + 1 + 1)


# ---------------------------------------------------------------------------
# Weighted-fair queue: DRR bounds, tier priority
# ---------------------------------------------------------------------------


def test_wfq_weighted_shares_within_drr_bound():
    """Backlogged tenants with weights 3:1 are served 3:1, never lagging
    their ideal share by more than one quantum's worth."""
    q = WeightedFairQueue()
    weights = {"big": 3.0, "small": 1.0}
    for i in range(40):
        q.push(0, "big", f"b{i}")
        q.push(0, "small", f"s{i}")
    served = {"big": 0, "small": 0}
    for step in range(1, 33):
        tenant, _ = q.pop(weight_of=lambda t: weights[t])
        served[tenant] += 1
        # DRR bound: each backlogged tenant's service is within one
        # quantum (weight normalized) of its ideal share at every step.
        total = served["big"] + served["small"]
        for t, w in weights.items():
            ideal = total * w / sum(weights.values())
            assert abs(served[t] - ideal) <= max(weights.values()) + 1.0
    assert served["big"] == pytest.approx(3 * served["small"], abs=4)


def test_wfq_strict_tier_priority():
    q = WeightedFairQueue()
    q.push(tier_rank(TIER_BATCH), "crawler", "batch-0")
    q.push(tier_rank(TIER_INTERACTIVE), "acme", "live-0")
    q.push(tier_rank(TIER_BATCH), "crawler", "batch-1")
    q.push(tier_rank(TIER_INTERACTIVE), "acme", "live-1")
    order = [q.pop()[1] for _ in range(4)]
    assert order == ["live-0", "live-1", "batch-0", "batch-1"]


def test_wfq_dry_tenant_skipped_without_losing_credit():
    q = WeightedFairQueue()
    q.push(0, "dry", "d0")
    q.push(0, "wet", "w0")
    got = q.pop(ready=lambda t: t != "dry")
    assert got == ("wet", "w0")
    # Dry tenant still queued, servable once ready.
    assert q.pop() == ("dry", "d0")


def test_wfq_idle_tenant_banks_no_credit():
    """A tenant that drains must not accumulate deficit while idle (DRR
    memoryless property — otherwise a quiet tenant could burst past its
    share afterwards)."""
    q = WeightedFairQueue()
    q.push(0, "a", "a0")
    assert q.pop() == ("a", "a0")
    assert ("a" not in {t for _, t in q.tenants_waiting()})
    q.push(0, "a", "a1")
    q.push(0, "b", "b0")
    # Fresh deficits: service alternates rather than 'a' bursting.
    first, _ = q.pop()
    second, _ = q.pop()
    assert {first, second} == {"a", "b"}


# ---------------------------------------------------------------------------
# Per-tenant buckets: refill isolation + replica share rescale
# ---------------------------------------------------------------------------


def _tenant_controller(rate=8.0, **kw):
    cfg = TenantConfig({
        "victim": TenantSpec("victim", weight=1.0),
        "flooder": TenantSpec("flooder", weight=1.0),
    })
    return AdmissionController(rate=rate, tenants=cfg, **kw), cfg


def test_tenant_bucket_refill_isolation():
    """The flooder draining ITS bucket never touches the victim's."""
    ctl, cfg = _tenant_controller(rate=9.0)  # 3 weights -> 3 rps each
    flooder = ctl.tenant_bucket(cfg.tenants["flooder"])
    victim = ctl.tenant_bucket(cfg.tenants["victim"])
    t = 1000.0
    while flooder.try_acquire(t):
        pass  # flood: drain every flooder token
    assert not flooder.try_acquire(t)
    # Victim's bucket is untouched: full burst available.
    assert victim.try_acquire(t)
    # And refill rates are independent weight shares.
    assert flooder.rate == pytest.approx(3.0)
    assert victim.rate == pytest.approx(3.0)


def test_tenant_explicit_rate_overrides_weight_share():
    cfg = TenantConfig({
        "capped": TenantSpec("capped", weight=10.0, rate=1.5),
    })
    ctl = AdmissionController(rate=100.0, tenants=cfg)
    assert ctl.tenant_bucket(cfg.tenants["capped"]).rate == pytest.approx(1.5)


class _ShareBackend:
    shared = True

    def __init__(self, share):
        self.share = share

    def admission_share(self):
        return self.share


def test_tenant_buckets_rescale_with_admission_share():
    """Router HA rate splitting applies per tenant: each tenant's
    fleet-wide guarantee splits across live replicas."""
    ctl, cfg = _tenant_controller(rate=9.0, state_backend=_ShareBackend(0.5))
    b = ctl.tenant_bucket(cfg.tenants["victim"])
    assert b.rate == pytest.approx(3.0)
    ctl._apply_share()
    assert b.rate == pytest.approx(1.5)  # half the share on 2 replicas
    ctl.state_backend.share = 1.0
    ctl._apply_share()
    assert b.rate == pytest.approx(3.0)  # peer died: full share reclaimed


async def test_admit_flood_sheds_only_flooder():
    """Concurrent flood far over the flooder's share: every victim admit
    goes through immediately; the flood overflow sheds with 429
    semantics charged to the flooder alone."""
    ctl, cfg = _tenant_controller(rate=9.0, max_queue=4, queue_timeout=0.15)
    flooder, victim = cfg.tenants["flooder"], cfg.tenants["victim"]
    flood = await asyncio.gather(
        *(ctl.admit(tenant=flooder) for _ in range(60))
    )
    shed = [d for d in flood if not d.admitted]
    assert shed, "a 60-request burst over a 3 rps share must shed"
    t0 = time.monotonic()
    victim_decisions = [await ctl.admit(tenant=victim) for _ in range(3)]
    assert all(d.admitted for d in victim_decisions)
    assert time.monotonic() - t0 < 0.5  # no queueing behind the flood
    ctl.close()


async def test_batch_tier_never_served_ahead_of_interactive():
    """With both tenants' buckets dry and refilling identically, every
    refill tick grants the queued interactive waiter before the batch
    one — batch still drains at its OWN share (it is never starved of
    it), but it never jumps interactive at a grant point."""
    cfg = TenantConfig({
        "live": TenantSpec("live", weight=1.0, tier=TIER_INTERACTIVE),
        "bulk": TenantSpec("bulk", weight=1.0, tier=TIER_BATCH),
    })
    ctl = AdmissionController(rate=30.0, max_queue=64, queue_timeout=5.0,
                              tenants=cfg)
    # Drain both buckets to the SAME anchor so they refill in lockstep.
    now = time.monotonic()
    for spec in (cfg.tenants["live"], cfg.tenants["bulk"]):
        b = ctl.tenant_bucket(spec)
        b.tokens = 0.0
        b.last_refill = now
    order = []

    async def one(spec, tag):
        d = await ctl.admit(tenant=spec)
        if d.admitted:
            order.append(tag)

    tasks = [asyncio.create_task(one(cfg.tenants["bulk"], f"b{i}"))
             for i in range(3)]
    await asyncio.sleep(0.02)  # batch queued first
    tasks += [asyncio.create_task(one(cfg.tenants["live"], f"l{i}"))
              for i in range(3)]
    await asyncio.gather(*tasks)
    assert len(order) == 6
    # Prefix property: at every point, interactive grants >= batch
    # grants — within each tick the interactive waiter went first.
    for k in range(1, len(order) + 1):
        live_n = sum(1 for t in order[:k] if t.startswith("l"))
        assert live_n >= k - live_n
    ctl.close()


def test_adhoc_names_share_one_bucket():
    """Rotating invented tenant names must not mint admission rate: every
    ad-hoc name draws from the ONE default-slice bucket."""
    cfg = TenantConfig()
    ctl = AdmissionController(rate=9.0, tenants=cfg)
    b1 = ctl.tenant_bucket(cfg.resolve({"X-PST-Tenant": "invented-1"}))
    b2 = ctl.tenant_bucket(cfg.resolve({"X-PST-Tenant": "invented-2"}))
    assert b1 is b2  # same underlying (default) bucket
    t = 1000.0
    while b1.try_acquire(t):
        pass
    # A fresh name gets no fresh tokens.
    b3 = ctl.tenant_bucket(cfg.resolve({"X-PST-Tenant": "invented-3"}))
    assert not b3.try_acquire(t)


def test_header_cannot_impersonate_key_protected_tenant():
    """A configured tenant with api_keys can only be claimed by one of
    them: a bare header naming it resolves to the default tenant (no
    stolen contract, no usage billed to the victim)."""
    cfg = TenantConfig({
        "premium": TenantSpec("premium", weight=10.0, api_keys=("sk-p",)),
        "open-team": TenantSpec("open-team", weight=2.0),  # no keys
    })
    spoofed = cfg.resolve({"X-PST-Tenant": "premium"})
    assert spoofed.name == "default"
    # The real key still works, and keyless configured tenants stay
    # header-claimable (trusted-gateway mode).
    assert cfg.resolve({}, api_key="sk-p").name == "premium"
    assert cfg.resolve({"X-PST-Tenant": "open-team"}).name == "open-team"


def test_adhoc_metric_label_collapses_to_other():
    """Wire-controlled names never become Prometheus label values: the
    ad-hoc population shares the 'other' label (label children are never
    evicted, so attacker names would leak router memory)."""
    cfg = TenantConfig({"acme": TenantSpec("acme")})
    assert cfg.resolve({"X-PST-Tenant": "acme"}).label == "acme"
    assert cfg.resolve({"X-PST-Tenant": "whatever-9f3a"}).label == "other"
    assert cfg.resolve({}).label == "default"


def test_deficit_scheduler_credit_is_bounded():
    """A tenant charged while running solo must not bank unbounded debt:
    when a competitor appears it is behind by at most the clamp, not by
    its whole history."""
    from production_stack_tpu.resilience.tenancy import DeficitScheduler

    drr = DeficitScheduler()
    for _ in range(1000):
        drr.charge("solo")  # solo admissions never go through pick()
    # Contested picks: solo must win a turn within ~2x the clamp bound.
    wins_before_solo = 0
    for _ in range(32):
        pick = drr.pick({"solo": 1.0, "newcomer": 1.0})
        drr.charge(pick)
        if pick == "solo":
            break
        wins_before_solo += 1
    assert wins_before_solo <= 2 * DeficitScheduler.CREDIT_BOUND + 1


def test_session_pin_tier_never_downgrades():
    pins = scoring.SessionPins(max_pins=2)
    pins.pin("s1", "http://e1")                       # interactive
    pins.pin("s1", "http://e1", batch_tier=True)      # batch re-pin
    pins.pin("s2", "http://e2", batch_tier=True)
    pins.pin("s3", "http://e3")                       # over capacity
    # s2 (genuinely batch) evicts first; s1 kept its interactive tier.
    assert pins.get("s2") is None
    assert pins.get("s1") == "http://e1"


# ---------------------------------------------------------------------------
# Engine scheduler: tier admission, batch preemption, queue ages
# ---------------------------------------------------------------------------


def _seq(rid, n_tokens=8, tenant="default", tier="interactive",
         max_tokens=4):
    return Sequence(
        rid, list(range(n_tokens)), SamplingParams(max_tokens=max_tokens),
        tenant=tenant, tenant_class=tier,
    )


def _sched(num_blocks=16, block_size=4, max_num_seqs=8, fairness=True):
    alloc = BlockAllocator(num_blocks=num_blocks, block_size=block_size)
    return Scheduler(
        SchedulerConfig(
            max_num_seqs=max_num_seqs, max_prefill_tokens=64,
            max_model_len=64, tenant_fairness=fairness,
        ),
        alloc,
    ), alloc


def test_scheduler_interactive_admits_before_earlier_batch():
    sched, _ = _sched(max_num_seqs=1)
    sched.add(_seq("batch", tenant="bulk", tier="batch"))
    sched.add(_seq("live", tenant="acme", tier="interactive"))
    out = sched.schedule()
    assert [s.seq.request_id for s in out.prefills] == ["live"]
    assert [s.request_id for s in sched.running] == ["live"]
    # The per-tenant queue-age signal: batch pressure queues BATCH work;
    # the interactive queue age stays zero (nothing interactive waits).
    ages = sched.queue_age_by_tier()
    assert ages["interactive"] == 0.0
    assert ages["batch"] > 0.0


def test_scheduler_fifo_unchanged_when_homogeneous():
    sched, _ = _sched(max_num_seqs=2)
    sched.add(_seq("first"))
    sched.add(_seq("second"))
    out = sched.schedule()
    assert [p.seq.request_id for p in out.prefills] == ["first", "second"]


def test_scheduler_fairness_off_is_plain_fifo():
    sched, _ = _sched(max_num_seqs=1, fairness=False)
    sched.add(_seq("batch", tenant="bulk", tier="batch"))
    sched.add(_seq("live", tenant="acme", tier="interactive"))
    out = sched.schedule()
    assert [s.seq.request_id for s in out.prefills] == ["batch"]


def test_scheduler_drr_alternates_tenants_within_tier():
    sched, _ = _sched(max_num_seqs=3)
    sched.add(_seq("a1", tenant="a"))
    sched.add(_seq("a2", tenant="a"))
    sched.add(_seq("b1", tenant="b"))
    sched.schedule()
    admitted = [s.request_id for s in sched.running]
    # All admitted (capacity 3), but never both of a's before b's head.
    assert set(admitted) == {"a1", "a2", "b1"}
    assert admitted.index("b1") < admitted.index("a2")


def test_batch_preemption_releases_pages_for_interactive():
    """Pool full of batch-tier decode work; an interactive arrival must
    preempt it (pages actually released) instead of waiting."""
    sched, alloc = _sched(num_blocks=8, block_size=4, max_num_seqs=4)
    # Batch sequence holding most of the pool: 24 prompt tokens = 6 pages.
    sched.add(_seq("bulk", n_tokens=24, tenant="crawler", tier="batch"))
    out = sched.schedule()
    assert [p.seq.request_id for p in out.prefills] == ["bulk"]
    for p in out.prefills:
        p.seq.num_computed_tokens = p.end
    free_before = alloc.num_free
    assert free_before < 6  # pool nearly exhausted
    # Interactive arrival needing more pages than remain free.
    sched.add(_seq("live", n_tokens=16, tenant="acme", tier="interactive"))
    out = sched.schedule()
    assert "live" in [s.request_id for s in sched.running]
    assert "bulk" not in [s.request_id for s in sched.running]
    assert sched.batch_preemptions == 1
    # The batch victim's pages were genuinely surrendered.
    assert not [b for b in out.preempted if b.request_id == "live"]
    stats_ages = sched.queue_age_by_tier()
    assert set(stats_ages) == {"interactive", "batch"}


def test_interactive_never_preempted_while_batch_remains():
    sched, alloc = _sched(num_blocks=8, block_size=4, max_num_seqs=4)
    sched.add(_seq("live", n_tokens=12, tenant="acme", tier="interactive"))
    sched.add(_seq("bulk", n_tokens=12, tenant="crawler", tier="batch"))
    out = sched.schedule()
    for p in out.prefills:
        p.seq.num_computed_tokens = p.end
    # Force page pressure: a second interactive that cannot fit.
    sched.add(_seq("live2", n_tokens=12, tenant="acme", tier="interactive"))
    sched.schedule()
    running = [s.request_id for s in sched.running]
    assert "live" in running
    assert "bulk" not in running  # the batch seq was the victim


# ---------------------------------------------------------------------------
# Fleet state: class-aware pins + batch bounded-load behavior
# ---------------------------------------------------------------------------


def test_session_pins_evict_batch_first():
    pins = scoring.SessionPins(max_pins=3)
    pins.pin("i1", "http://e1")                      # oldest interactive
    pins.pin("b1", "http://e2", batch_tier=True)
    pins.pin("i2", "http://e3")
    pins.pin("i3", "http://e4")                      # over capacity
    # The batch pin dies first even though i1 is LRU-older.
    assert pins.get("b1") is None
    assert pins.get("i1") == "http://e1"
    # With no batch pins left, plain LRU applies.
    pins.pin("i4", "http://e5")
    assert pins.get("i1") is None


def test_pick_bounded_batch_saturated_takes_least_loaded():
    scores = {"hot": 100.0, "cold": 1.0}
    loads = {"hot": 50.0, "cold": 10.0}
    bound = 5.0  # everyone saturated
    # Interactive fails open to the best scorer (affinity wins).
    url, reason = scoring.pick_bounded(scores, loads, bound)
    assert (url, reason) == ("hot", "saturated")
    # Batch may not pin past the bound: least-loaded instead.
    url, reason = scoring.pick_bounded(scores, loads, bound, batch_tier=True)
    assert (url, reason) == ("cold", "saturated")


# ---------------------------------------------------------------------------
# Canary TTFT gossip: replica scoring agreement
# ---------------------------------------------------------------------------


def test_canary_ttft_gossips_and_merges_pessimistically(monkeypatch):
    a = GossipStateBackend(peers=["http://b"], replica_id="ra")
    b = GossipStateBackend(peers=["http://a"], replica_id="rb")
    # Replica B's prober saw engine e1 fail (timeout recorded); A's saw
    # it healthy.
    b.register_provider("canary_ttft", lambda: {"http://e1": 5.0})
    a.register_provider("canary_ttft", lambda: {"http://e1": 0.02,
                                                "http://e2": 0.03})
    a._apply(b.digest())
    b._apply(a.digest())
    assert a.peer_canary_ttfts()["rb"]["http://e1"] == 5.0
    assert b.peer_canary_ttfts()["ra"]["http://e2"] == 0.03

    # Both replicas' FLEET scoring views agree on e1 being slow.
    from production_stack_tpu.router.routing.logic import FleetRouter
    from production_stack_tpu.router import state as state_mod
    from production_stack_tpu.router.services import canary as canary_mod

    class _Prober:
        def __init__(self, view):
            self._view = view

        def ttft_view(self):
            return dict(self._view)

    def merged_view(backend, local):
        monkeypatch.setattr(state_mod, "get_state_backend", lambda: backend)
        monkeypatch.setattr(
            canary_mod, "get_canary_prober", lambda: _Prober(local)
        )
        return FleetRouter()._canary_ttfts()

    view_a = merged_view(a, {"http://e1": 0.02, "http://e2": 0.03})
    view_b = merged_view(b, {"http://e1": 5.0})
    assert view_a["http://e1"] == 5.0  # A adopted B's failure verdict
    assert view_b["http://e1"] == 5.0
    assert view_a["http://e2"] == 0.03
    assert view_b["http://e2"] == 0.03  # B adopted A's healthy sample


# ---------------------------------------------------------------------------
# Fake engine: tenant-scoped fault injection
# ---------------------------------------------------------------------------


async def _start_site(app):
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}"


async def test_fake_engine_tenant_scoped_fault():
    app = create_fake_engine_app(model=MODEL, speed=5000)
    runner, url = await _start_site(app)
    try:
        async with aiohttp.ClientSession() as s:
            await s.post(f"{url}/admin/fail",
                         json={"mode": "error", "tenant": "flooder"})

            async def gen(tenant):
                async with s.post(
                    f"{url}/v1/completions",
                    json={"model": MODEL, "prompt": "hi", "max_tokens": 2},
                    headers={"X-PST-Tenant": tenant},
                ) as r:
                    return r.status

            assert await gen("flooder") == 500
            assert await gen("victim") == 200   # untouched
            assert await gen("flooder") == 500  # fault persists (count -1)
            await s.post(f"{url}/admin/heal")
            assert await gen("flooder") == 200
        state = app["state"]
        assert {t["tenant"] for t in state.tenants_seen} == {
            "flooder", "victim"
        }
    finally:
        await runner.cleanup()


# ---------------------------------------------------------------------------
# In-process e2e: stamping, metering, flood isolation (1 and 2 replicas)
# ---------------------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _tenant_file(tmp_path):
    path = tmp_path / "tenants.json"
    path.write_text(json.dumps({
        "tenants": {
            "victim": {"weight": 1, "tier": "interactive"},
            "flooder": {"weight": 1, "tier": "interactive"},
            "crawler": {"weight": 1, "tier": "batch"},
        }
    }))
    return str(path)


class TenantCluster:
    """One fake engine + N router replicas with tenant isolation on."""

    def __init__(self, tenant_file, replicas=1, rate=30.0, extra=None):
        self.tenant_file = tenant_file
        self.replicas = replicas
        self.rate = rate
        self.extra = extra or []
        self.runners = []
        self.router_urls = []
        self.engine_app = None

    async def __aenter__(self):
        self.engine_app = create_fake_engine_app(
            model=MODEL, speed=5000, ttft=0.05
        )
        runner, engine_url = await _start_site(self.engine_app)
        self.runners.append(runner)
        ports = [_free_port() for _ in range(self.replicas)]
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        for i, port in enumerate(ports):
            argv = [
                "--service-discovery", "static",
                "--static-backends", engine_url,
                "--static-models", MODEL,
                "--engine-stats-interval", "0.2",
                "--tenant-isolation",
                "--tenant-config", self.tenant_file,
                "--admission-rate", str(self.rate),
                "--admission-queue-timeout", "0.3",
                *self.extra,
            ]
            if self.replicas > 1:
                peers = ",".join(u for j, u in enumerate(urls) if j != i)
                argv += ["--state-backend", "gossip",
                         "--state-peers", peers,
                         "--state-sync-interval", "0.1",
                         "--state-peer-timeout", "1.0",
                         "--state-replica-id", f"r{i}"]
            app = create_app(parse_args(argv))
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", port)
            await site.start()
            self.runners.append(runner)
            self.router_urls.append(urls[i])
            # Each create_app rebinds ambient scope; keep going.
        if self.replicas > 1:
            await asyncio.sleep(0.4)  # let gossip converge membership
        return self

    async def __aexit__(self, *exc):
        for runner in reversed(self.runners):
            await runner.cleanup()
        reset_router_singletons()


async def _timed_completion(session, url, tenant, prompt="hello there"):
    t0 = time.monotonic()
    async with session.post(
        f"{url}/v1/completions",
        json={"model": MODEL, "prompt": prompt, "max_tokens": 2},
        headers={"X-PST-Tenant": tenant},
    ) as resp:
        await resp.read()
        return resp.status, time.monotonic() - t0


async def _victim_phase(session, urls, n=14, pace=0.1):
    for i in range(n):
        status, _ = await _timed_completion(
            session, urls[i % len(urls)], "victim"
        )
        assert status == 200, "victim traffic must never shed"
        await asyncio.sleep(pace)
    return n


async def _flood(session, urls, stop, rate=100.0):
    """Fire-and-forget flooder traffic at ~rate rps until stop is set."""
    tasks = []
    i = 0
    while not stop.is_set():
        tasks.append(asyncio.create_task(
            _timed_completion(session, urls[i % len(urls)], "flooder")
        ))
        i += 1
        await asyncio.sleep(1.0 / rate)
    results = await asyncio.gather(*tasks, return_exceptions=True)
    statuses = [r[0] for r in results if isinstance(r, tuple)]
    return statuses


async def _flood_isolation(replicas, tmp_path, monkeypatch):
    # Every request that waited in a router's admission queue, as (tenant,
    # requests already standing in the queue it joined): a request stands
    # behind another only there.
    queued = []
    push = WeightedFairQueue.push
    monkeypatch.setattr(
        WeightedFairQueue, "push",
        lambda self, rank, tenant, item: (
            queued.append((tenant, self.depth(tenant))),
            push(self, rank, tenant, item))[1])
    async with TenantCluster(_tenant_file(tmp_path),
                             replicas=replicas) as c:
        async with aiohttp.ClientSession() as s:
            sent = await _victim_phase(s, c.router_urls)
            stop = asyncio.Event()
            flood_task = asyncio.create_task(
                _flood(s, c.router_urls, stop)
            )
            await asyncio.sleep(0.2)  # flood established
            sent += await _victim_phase(s, c.router_urls)
            stop.set()
            statuses = await flood_task
            metrics_texts = []
            for url in c.router_urls:
                async with s.get(f"{url}/metrics") as r:
                    metrics_texts.append(await r.text())
        reached = [t["tenant"] for t in c.engine_app["state"].tenants_seen]
    # The flood really was a flood: far over its share, so most of it
    # shed (its own bucket/queue, 429s).
    assert statuses.count(429) > len(statuses) * 0.5
    # The guarantee, in what the victim's requests were answered with and
    # where they stood (no clock of a loaded sandbox is read): every one
    # was answered 200 (asserted as it came) and reached the engine, and
    # where one waited for its own bucket (a replica's share of the rate)
    # nothing stood before it: no flood request, which queued, and shed,
    # in a queue of its own.
    assert reached.count("victim") == sent
    assert [d for who, d in queued if who == "victim" and d] == [], queued
    assert max(d for who, d in queued if who == "flooder") > 0
    assert {who for who, _ in queued} <= {"victim", "flooder"}
    # Per-tenant accounting on the router metrics surface.
    joined = "\n".join(metrics_texts)
    assert 'pst_tenant_sheds_total{' in joined
    assert 'tenant="flooder"' in joined
    assert 'pst_tenant_usage_tokens_total{' in joined


async def test_tenant_flood_isolation_single_replica(tmp_path, monkeypatch):
    await _flood_isolation(1, tmp_path, monkeypatch)


async def test_tenant_flood_isolation_two_replicas(tmp_path, monkeypatch):
    """Same guarantee on two gossiping replicas: each tenant's rate is
    split across replicas and the victim still never queues."""
    await _flood_isolation(2, tmp_path, monkeypatch)


async def test_tenant_stamp_overwrites_client_class(tmp_path):
    """A client may not self-assign a tier: the router re-stamps the
    canonical headers from its own config on every upstream hop."""
    async with TenantCluster(_tenant_file(tmp_path)) as c:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"{c.router_urls[0]}/v1/completions",
                json={"model": MODEL, "prompt": "hi", "max_tokens": 2},
                headers={"X-PST-Tenant": "victim",
                         "X-PST-Tenant-Class": "batch"},  # spoof attempt
            ) as resp:
                assert resp.status == 200
        seen = c.engine_app["state"].tenants_seen[-1]
        assert seen["tenant"] == "victim"
        # victim is configured interactive: the spoofed batch class died
        # at the router.
        assert seen["tenant_class"] == "interactive"


async def test_batch_tenant_stamped_batch_class(tmp_path):
    async with TenantCluster(_tenant_file(tmp_path)) as c:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"{c.router_urls[0]}/v1/completions",
                json={"model": MODEL, "prompt": "hi", "max_tokens": 2},
                headers={"X-PST-Tenant": "crawler"},
            ) as resp:
                assert resp.status == 200
        seen = c.engine_app["state"].tenants_seen[-1]
        assert seen == {"tenant": "crawler", "tenant_class": "batch"}


async def test_tenant_usage_metering_nonstream_and_stream(tmp_path):
    async with TenantCluster(_tenant_file(tmp_path)) as c:
        url = c.router_urls[0]
        async with aiohttp.ClientSession() as s:
            # Non-streamed: usage parsed from the JSON body.
            async with s.post(
                f"{url}/v1/completions",
                json={"model": MODEL, "prompt": "one two three",
                      "max_tokens": 4},
                headers={"X-PST-Tenant": "victim"},
            ) as resp:
                assert resp.status == 200
            # Streamed: usage accumulated by the journal.
            async with s.post(
                f"{url}/v1/completions",
                json={"model": MODEL, "prompt": "four five", "stream": True,
                      "max_tokens": 4},
                headers={"X-PST-Tenant": "victim"},
            ) as resp:
                assert resp.status == 200
                await resp.read()
            async with s.get(f"{url}/metrics") as r:
                text = await r.text()
    in_line = [
        ln for ln in text.splitlines()
        if ln.startswith("pst_tenant_usage_tokens_total")
        and 'direction="in"' in ln and 'tenant="victim"' in ln
    ]
    out_line = [
        ln for ln in text.splitlines()
        if ln.startswith("pst_tenant_usage_tokens_total")
        and 'direction="out"' in ln and 'tenant="victim"' in ln
    ]
    assert in_line and float(in_line[0].rsplit(" ", 1)[1]) > 0
    assert out_line and float(out_line[0].rsplit(" ", 1)[1]) >= 8  # 2x4 toks


async def test_tenant_deadline_default_applies(tmp_path):
    """A tenant deadline_ms default reaches the engine as a propagated
    budget header."""
    path = tmp_path / "t.json"
    path.write_text(json.dumps({
        "tenants": {"tight": {"weight": 1, "deadline_ms": 30000}}
    }))
    async with TenantCluster(str(path)) as c:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"{c.router_urls[0]}/v1/completions",
                json={"model": MODEL, "prompt": "hi", "max_tokens": 2},
                headers={"X-PST-Tenant": "tight"},
            ) as resp:
                assert resp.status == 200
        deadlines = c.engine_app["state"].deadlines_seen
        assert deadlines and deadlines[-1] is not None
        assert 0 < float(deadlines[-1]) <= 30000
