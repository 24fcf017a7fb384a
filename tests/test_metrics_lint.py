"""Metrics convention lint (ISSUE 6 satellite): walk every
CollectorRegistry the codebase builds and fail on drift.

Rules enforced:

  * a family whose name ends in `_total` must actually be a counter
    (the pre-ISSUE-6 drift: fleet-summed monotonic series exported as
    Gauges wearing `_total` names — `rate()` consumers saw
    `# TYPE ... gauge`);
  * histogram families must carry a unit suffix (`_seconds` / `_bytes`
    / `_ms`);
  * a metric name appearing in more than one registry (frontend,
    metrics component, standalone router, system status) must be an
    INTENTIONALLY shared series — listed below with a matching type —
    otherwise two processes are exporting colliding semantics.

New registries/metrics must either follow the conventions or make a
deliberate, reviewed entry in the shared-series allowlist.
"""

from prometheus_client import CollectorRegistry

from dynamo_tpu.components.metrics import MetricsComponent
from dynamo_tpu.http.metrics import ServiceMetrics
from dynamo_tpu.router import build_router_registry
from dynamo_tpu.runtime.http_server import SystemStatusServer
from dynamo_tpu.runtime.protocols import EndpointId
from dynamo_tpu.telemetry.goodput import WASTE_CAUSES, GoodputLedger

# Series deliberately exported by several roles (same meaning, different
# process — normal Prometheus federation, distinguished by instance).
INTENTIONALLY_SHARED = {
    # per-process runtime health (every SystemStatusServer)
    "dyn_runtime_uptime_seconds",
    "dyn_runtime_health",
    # KV routing quality: frontend (in-process router), metrics
    # component (event plane), standalone router (own scheduler)
    "dyn_llm_kv_hit_rate",
    "dyn_llm_kv_matched_blocks",
    # fleet prefix cache (ISSUE 17): fleet-best match rate and realized
    # peer-pull outcomes — frontend (attach), metrics component (fleet
    # scrape truth), standalone router (zero-stable planning side)
    "dyn_llm_kv_fleet_hit_rate",
    "dyn_llm_kv_pulled_blocks",
    # admission-control sheds: frontend and standalone router
    "dyn_llm_requests_shed",
    # deadline expiries: frontend observation vs fleet-summed worker count
    "dyn_llm_deadline_exceeded",
    # brownout rung: frontend ladder vs fleet-worst worker rung
    "dyn_llm_brownout_level",
    # QoS counters: colocated-engine attach on the frontend vs the
    # fabric-scraped fleet sums on the metrics component
    "dyn_llm_preemptions",
    "dyn_llm_preempted_too_often",
    "dyn_llm_brownout_sheds",
    # integrity plane (ISSUE 8): the frontend exports its own process
    # counters (dispatch-plane fenced rejects), the metrics component the
    # fabric-scraped fleet sums — same meaning, different scope
    "dyn_llm_kv_integrity_failures",
    "dyn_llm_blocks_quarantined",
    "dyn_llm_fenced_rejects",
    # control plane (ISSUE 10): every process exports its OWN fabric
    # client's health — connected flag, degraded mode, time degraded,
    # blackout count (frontend + metrics component)
    "dyn_fabric_connected",
    "dyn_fabric_blackouts",
    "dyn_llm_degraded_mode",
    "dyn_llm_degraded_seconds",
    # closed-loop fleet plane (ISSUE 11): the planner publishes one
    # status; the metrics component (fabric scrape) and any frontend
    # (PlannerStatusCache attach) render the SAME families from it
    "dyn_planner_decisions",
    "dyn_planner_frozen",
    "dyn_planner_replicas_target",
    "dyn_planner_replicas_actual",
    "dyn_supervisor_restarts",
    "dyn_supervisor_quarantined",
    # tail-tolerance plane (ISSUE 12): frontend (consumer-observed +
    # self-reported scorer), metrics component (fleet scrape scorer),
    # and standalone router (its own scorer) all export the score and
    # ejection families; hedge families are frontend-only (hedging
    # happens where dispatch happens)
    "dyn_llm_worker_health_score",
    "dyn_llm_workers_ejected",
    "dyn_llm_ejections",
    # goodput ledger (ISSUE 14): colocated-engine attach on the frontend
    # vs the fleet-merged view on the metrics component — same families,
    # merged views add (histograms bucket-add, counters sum)
    "dyn_llm_step_duration_seconds",
    "dyn_llm_steps",
    "dyn_llm_step_occupancy",
    "dyn_llm_phase_bubble_seconds",
    "dyn_llm_device_tokens",
    # unified mixed prefill+decode steps (ISSUE 16) ride the same
    # shared goodput surface
    "dyn_llm_mixed_steps",
    "dyn_llm_mixed_step_tokens",
    "dyn_llm_tokens_wasted",
    "dyn_llm_recompiles",
    "dyn_llm_compile_seconds",
    # expert-layer counters of a sparse-expert model (ISSUE 28) ride the
    # same shared goodput surface
    "dyn_llm_moe_layer_steps",
    "dyn_llm_moe_assignments",
    "dyn_llm_moe_experts_touched",
    "dyn_llm_moe_max_expert_load",
    # a held share of the experts (ISSUE 46): every assignment the router made
    "dyn_llm_moe_assignments_made",
    # the sampler's candidate pool (ISSUE 32): how often a dispatch's lanes
    # made the device compute it; the same shared goodput surface
    "dyn_llm_sampler_dispatches",
    "dyn_llm_sampler_pool_dispatches",
    # and the log-prob surface (ISSUE 53)
    "dyn_llm_sampler_logprob_dispatches",
    # recurrent layers' state slots (ISSUE 38): decode steps, live slots,
    # resets and scanned prompt tokens; the same shared goodput surface
    "dyn_llm_ssm_layer_steps",
    "dyn_llm_ssm_slots_live",
    "dyn_llm_ssm_slot_resets",
    "dyn_llm_ssm_scan_tokens",
    # the two page groups' pools (ISSUE 52): rows a step's window and full
    # layers must read, blocks held, in use and given back; the same surface
    "dyn_llm_pool_decode_steps",
    "dyn_llm_pool_lane_steps",
    "dyn_llm_pool_window_rows",
    "dyn_llm_pool_full_rows",
    "dyn_llm_pool_lanes_past_window",
    "dyn_llm_pool_window_blocks_past",
    "dyn_llm_pool_window_in_use_steps",
    "dyn_llm_pool_window_capacity_steps",
    "dyn_llm_pool_full_in_use_steps",
    "dyn_llm_pool_full_capacity_steps",
    "dyn_llm_pool_window_blocks_given_back",
    # the stream edge (ISSUE 39): items put on sequences' streams and the
    # tokens they carried; the same shared goodput surface
    "dyn_llm_stream_items",
    "dyn_llm_stream_tokens",
    # the launch at the host's edge of the device (ISSUE 40): dispatches,
    # host arrays and bytes committed, bytes read back; the same surface
    "dyn_llm_launch_dispatches",
    "dyn_llm_launch_upload_arrays",
    "dyn_llm_launch_upload_bytes",
    "dyn_llm_launch_fetch_bytes",
    "dyn_llm_launch_chained",
    # decision provenance plane (ISSUE 20): every control-plane process
    # (frontend, metrics component, standalone router) exports its OWN
    # ledger's decision counts — decisions are made where they are
    # recorded, fleet totals come from summing scrapes
    "dyn_llm_decisions",
    "dyn_llm_decision_ring_dropped",
}

UNIT_SUFFIXES = ("_seconds", "_bytes", "_ms", "_ratio")


class _StubScheduler:
    hit_stats = {"decisions": 0, "isl_blocks": 0, "matched_blocks": 0,
                 "fleet_blocks": 0}
    hit_rate = 0.0
    fleet_hit_rate = 0.0
    pull_stats = {"plans": 0, "planned_blocks": 0}


class _StubHealth:
    ejections_total = {"first_frame": 0}

    def scores(self):
        return {1: 1.0}

    def ejected(self):
        return set()


class _StubHedger:
    outcomes = {"won": 0, "lost": 0, "budget_denied": 0}
    wasted_tokens = 0


class _StubBrownout:
    level = 0
    transitions = 0


class _StubComponent:
    """MetricsComponent only touches the component at start(); registry
    construction needs nothing from it."""


def _all_registries() -> dict[str, CollectorRegistry]:
    frontend = ServiceMetrics()
    # include every lazily-attached family in the lint surface
    frontend.attach_spec_stats({"num_drafts": 0, "num_draft_tokens": 0,
                                "num_accepted_tokens": 0})
    frontend.attach_kv_transfer_stats({})
    frontend.attach_kv_hit_stats(_StubScheduler())
    frontend.attach_health(_StubHealth(), _StubHedger())
    frontend.attach_brownout(_StubBrownout())
    frontend.attach_engine_qos(
        {"preemptions_by_class": {}, "preempted_too_often": 0,
         "shed_brownout": 0}
    )
    frontend.attach_integrity(
        {"integrity_failures_by_path": {"disagg_frame": 0},
         "blocks_quarantined": 0,
         "fenced_rejects_by_plane": {"dispatch": 0}}
    )
    frontend.attach_control_plane(
        {"connected": True, "degraded": False,
         "degraded_seconds_total": 0.0, "blackouts_total": 0,
         "buffered_publishes": 0, "flushed_publishes": 0,
         "dropped_publishes": 0}
    )
    frontend.attach_goodput(
        {"goodput": GoodputLedger(enabled=True)}, _StubHedger()
    )
    frontend.attach_planner(
        {"decisions_total": {"up|sla": 1}, "frozen": 0,
         "replicas_target": {"decode_worker": 1},
         "replicas_actual": {"decode_worker": 1},
         "supervisor": {"restarts_total": 0, "quarantined": 0}}
    )
    component = MetricsComponent(
        _StubComponent(), EndpointId("lint", "backend", "generate")
    )
    return {
        "frontend": frontend.registry,
        "component": component.registry,
        "router": build_router_registry(
            _StubScheduler(), lambda: 0, lambda: 0, health=_StubHealth()
        ),
        "system": SystemStatusServer().registry,
    }


def _families(registry: CollectorRegistry):
    return list(registry.collect())


def test_total_suffix_implies_counter():
    problems = []
    for role, registry in _all_registries().items():
        for fam in _families(registry):
            if fam.name.endswith("_total") and fam.type != "counter":
                problems.append(f"{role}: {fam.name} is {fam.type}")
            # sample-level check too: a gauge sample must never be
            # named like a counter
            if fam.type != "counter":
                for s in fam.samples:
                    if s.name.endswith("_total"):
                        problems.append(
                            f"{role}: sample {s.name} on {fam.type} "
                            f"family {fam.name}"
                        )
    assert not problems, problems


def test_histograms_carry_unit_suffix():
    problems = []
    for role, registry in _all_registries().items():
        for fam in _families(registry):
            if fam.type == "histogram" and not fam.name.endswith(
                UNIT_SUFFIXES
            ):
                problems.append(f"{role}: histogram {fam.name} has no unit")
    assert not problems, problems


def test_no_unreviewed_duplicates_across_registries():
    seen: dict[str, tuple[str, str]] = {}  # name -> (role, type)
    problems = []
    for role, registry in _all_registries().items():
        for fam in _families(registry):
            prev = seen.get(fam.name)
            if prev is None:
                seen[fam.name] = (role, fam.type)
                continue
            prev_role, prev_type = prev
            if fam.name not in INTENTIONALLY_SHARED:
                problems.append(
                    f"{fam.name} exported by both {prev_role} and {role} "
                    "but not in INTENTIONALLY_SHARED"
                )
            elif fam.type != prev_type:
                problems.append(
                    f"{fam.name}: type drift {prev_role}={prev_type} "
                    f"vs {role}={fam.type}"
                )
    assert not problems, problems


def test_qos_families_present_with_correct_types():
    """ISSUE 7: the per-class `_total` counters and the brownout gauge
    must exist with the right semantics on their home registries."""
    regs = _all_registries()
    by_role = {
        role: {f.name: f for f in _families(reg)}
        for role, reg in regs.items()
    }
    # frontend: per-class shed counter + ladder gauge + transition counter
    fam = by_role["frontend"].get("dyn_llm_class_requests_shed")
    assert fam is not None and fam.type == "counter"
    fam = by_role["frontend"].get("dyn_llm_brownout_level")
    assert fam is not None and fam.type == "gauge"
    fam = by_role["frontend"].get("dyn_llm_brownout_transitions")
    assert fam is not None and fam.type == "counter"
    # metrics component: per-class preemption counter (priority label),
    # storm-guard counter, engine brownout sheds, fleet-worst rung gauge
    for name in (
        "dyn_llm_preemptions",
        "dyn_llm_preempted_too_often",
        "dyn_llm_brownout_sheds",
    ):
        fam = by_role["component"].get(name)
        assert fam is not None and fam.type == "counter", name
    fam = by_role["component"].get("dyn_llm_brownout_level")
    assert fam is not None and fam.type == "gauge"


def test_integrity_families_present_with_correct_types():
    """ISSUE 8: the integrity/fence counter families must exist with
    counter semantics on both the frontend (process counters) and the
    metrics component (fleet sums)."""
    regs = _all_registries()
    by_role = {
        role: {f.name: f for f in _families(reg)}
        for role, reg in regs.items()
    }
    for role in ("frontend", "component"):
        for name in (
            "dyn_llm_kv_integrity_failures",
            "dyn_llm_blocks_quarantined",
            "dyn_llm_fenced_rejects",
        ):
            fam = by_role[role].get(name)
            assert fam is not None and fam.type == "counter", (role, name)


def test_control_plane_families_present_with_correct_types():
    """ISSUE 10: the control-plane health families (degraded-mode data
    plane) must exist on both the frontend and the metrics component —
    reachability flags as gauges, degraded time / blackout count with
    counter semantics."""
    regs = _all_registries()
    by_role = {
        role: {f.name: f for f in _families(reg)}
        for role, reg in regs.items()
    }
    for role in ("frontend", "component"):
        for name, typ in (
            ("dyn_fabric_connected", "gauge"),
            ("dyn_llm_degraded_mode", "gauge"),
            ("dyn_llm_degraded_seconds", "counter"),
            ("dyn_fabric_blackouts", "counter"),
        ):
            fam = by_role[role].get(name)
            assert fam is not None and fam.type == typ, (role, name)
    # the buffered-publish flow is frontend-local (per-process client)
    for name in (
        "dyn_llm_degraded_publishes_buffered",
        "dyn_llm_degraded_publishes_flushed",
    ):
        fam = by_role["frontend"].get(name)
        assert fam is not None and fam.type == "counter", name


def test_planner_families_present_with_correct_types():
    """ISSUE 11: the closed-loop fleet families must exist with the
    right semantics on both the frontend (PlannerStatusCache attach) and
    the metrics component (fabric scrape of the planner's status key)."""
    regs = _all_registries()
    by_role = {
        role: {f.name: f for f in _families(reg)}
        for role, reg in regs.items()
    }
    for role in ("frontend", "component"):
        for name, typ in (
            ("dyn_planner_decisions", "counter"),
            ("dyn_planner_frozen", "gauge"),
            ("dyn_planner_replicas_target", "gauge"),
            ("dyn_planner_replicas_actual", "gauge"),
            ("dyn_supervisor_restarts", "counter"),
            ("dyn_supervisor_quarantined", "gauge"),
        ):
            fam = by_role[role].get(name)
            assert fam is not None and fam.type == typ, (role, name)


def test_fleet_upgrade_families_present_with_correct_types():
    """ISSUE 18: the rolling-upgrade families must exist with the right
    semantics on the metrics component (fabric scrape of the
    coordinator's ``fleet/upgrade-status`` key) — phase as a one-hot
    gauge over every coordinator phase, handoff blocks and rollbacks
    with counter semantics, replaced-count as a gauge. They are
    component-only: the coordinator publishes to the fabric, nothing
    attaches them to the frontend."""
    from dynamo_tpu.fleet.upgrade import PHASES

    regs = _all_registries()
    by_role = {
        role: {f.name: f for f in _families(reg)}
        for role, reg in regs.items()
    }
    for name, typ in (
        ("dyn_fleet_upgrade_phase", "gauge"),
        ("dyn_fleet_upgrade_handoff_blocks", "counter"),
        ("dyn_fleet_upgrade_rollbacks", "counter"),
        ("dyn_fleet_upgrade_replaced", "gauge"),
    ):
        fam = by_role["component"].get(name)
        assert fam is not None and fam.type == typ, (name, typ)
        for role in ("frontend", "router"):
            assert name not in by_role[role], (role, name)
    # the phase gauge is one-hot over the coordinator's state machine:
    # every phase labelled, exactly one sample set
    phase = by_role["component"]["dyn_fleet_upgrade_phase"]
    seen = {s.labels["phase"]: s.value for s in phase.samples}
    assert set(seen) == set(PHASES), seen
    assert sum(seen.values()) == 1.0, seen


def test_tail_families_present_with_correct_types():
    """ISSUE 12: the tail-tolerance families must exist with the right
    semantics — score/ejected as gauges, ejections/hedges/wasted-tokens
    as counters — on every role that exports them (hedge families are
    frontend-only: hedging happens where dispatch happens)."""
    regs = _all_registries()
    by_role = {
        role: {f.name: f for f in _families(reg)}
        for role, reg in regs.items()
    }
    for role in ("frontend", "component", "router"):
        for name, typ in (
            ("dyn_llm_worker_health_score", "gauge"),
            ("dyn_llm_workers_ejected", "gauge"),
            ("dyn_llm_ejections", "counter"),
        ):
            fam = by_role[role].get(name)
            assert fam is not None and fam.type == typ, (role, name)
    for name in ("dyn_llm_hedges", "dyn_llm_hedge_wasted_tokens"):
        fam = by_role["frontend"].get(name)
        assert fam is not None and fam.type == "counter", name
        for role in ("component", "router"):
            assert name not in by_role[role], (role, name)


def test_goodput_families_present_with_correct_types():
    """ISSUE 14: the goodput-ledger families must exist with the right
    semantics on both the frontend (colocated-engine attach) and the
    metrics component (fleet merge) — step durations as a real histogram,
    waste/recompiles/tokens/bubbles with counter semantics, occupancy and
    compile time as gauges."""
    regs = _all_registries()
    by_role = {
        role: {f.name: f for f in _families(reg)}
        for role, reg in regs.items()
    }
    for role in ("frontend", "component"):
        for name, typ in (
            ("dyn_llm_step_duration_seconds", "histogram"),
            ("dyn_llm_steps", "counter"),
            ("dyn_llm_step_occupancy", "gauge"),
            ("dyn_llm_phase_bubble_seconds", "counter"),
            ("dyn_llm_device_tokens", "counter"),
            ("dyn_llm_tokens_wasted", "counter"),
            ("dyn_llm_recompiles", "counter"),
            ("dyn_llm_compile_seconds", "gauge"),
        ):
            fam = by_role[role].get(name)
            assert fam is not None and fam.type == typ, (role, name)
    # the waste taxonomy exports ALL causes as stable zero-valued series
    # (dashboards must not see label churn on first waste)
    for role in ("frontend", "component"):
        fam = by_role[role]["dyn_llm_tokens_wasted"]
        causes = {s.labels.get("cause") for s in fam.samples}
        for cause in WASTE_CAUSES:
            assert cause in causes, (role, cause)


def test_prefix_cache_families_present_with_correct_types():
    """ISSUE 17: the fleet-prefix-cache families must exist with the
    right semantics — fleet hit rate as a gauge, pulled-blocks-by-outcome
    as a counter family with every outcome as a stable zero-valued
    series — on every role that exports them."""
    from dynamo_tpu.block_manager.peer import PULL_OUTCOMES

    regs = _all_registries()
    by_role = {
        role: {f.name: f for f in _families(reg)}
        for role, reg in regs.items()
    }
    for role in ("frontend", "component", "router"):
        fam = by_role[role].get("dyn_llm_kv_fleet_hit_rate")
        assert fam is not None and fam.type == "gauge", role
        fam = by_role[role].get("dyn_llm_kv_pulled_blocks")
        assert fam is not None and fam.type == "counter", role
        outcomes = {s.labels.get("outcome") for s in fam.samples}
        for key in PULL_OUTCOMES:
            assert key in outcomes, (role, key)
    # the router additionally exports its pull-planning counters
    for name in ("dyn_llm_kv_pull_plans", "dyn_llm_kv_pull_planned_blocks"):
        fam = by_role["router"].get(name)
        assert fam is not None and fam.type == "counter", name


def test_decision_families_present_with_correct_types():
    """ISSUE 20: the decision-provenance families must exist with counter
    semantics on every control-plane role (frontend, metrics component,
    standalone router), and the decisions family must pre-seed EVERY
    (actor, kind) pair of the closed taxonomy as stable zero-valued
    series — dashboards must not see label churn on first decision."""
    from dynamo_tpu.telemetry.provenance import TAXONOMY

    regs = _all_registries()
    by_role = {
        role: {f.name: f for f in _families(reg)}
        for role, reg in regs.items()
    }
    for role in ("frontend", "component", "router"):
        for name in ("dyn_llm_decisions", "dyn_llm_decision_ring_dropped"):
            fam = by_role[role].get(name)
            assert fam is not None and fam.type == "counter", (role, name)
        fam = by_role[role]["dyn_llm_decisions"]
        pairs = {
            (s.labels.get("actor"), s.labels.get("kind"))
            for s in fam.samples
        }
        for actor, kinds in TAXONOMY.items():
            for kind in kinds:
                assert (actor, kind) in pairs, (role, actor, kind)


def test_every_family_has_help_text():
    problems = []
    for role, registry in _all_registries().items():
        for fam in _families(registry):
            if not (fam.documentation or "").strip():
                problems.append(f"{role}: {fam.name} has empty HELP")
    assert not problems, problems
