"""Dashboard/alerts <-> code drift gates (no jax required).

The Grafana dashboard and the Prometheus rules are operational code:
a panel querying a metric nobody registers renders an empty chart
exactly when an operator needs it, and a registered metric nobody
charts is telemetry paying rent for nothing. These gates pin both
directions:

- every ``tpu:`` / ``tpu_router:`` series name referenced by a
  dashboard panel expr, an alert/recording rule, or a prom-adapter
  seriesQuery must be QUERYABLE from a metric registered in
  ``engine/metrics.py`` or ``router/services/metrics_service.py`` —
  including the sample-name suffix (a Counter registered as
  ``tpu:x`` exports ``tpu:x_total``; querying bare ``tpu:x`` silently
  matches nothing, which is exactly the drift class this catches);
- every registered ``tpu:``/``tpu_router:`` family must be referenced
  by the dashboard, the alert rules, or the explicit allowlist below
  (orphaned registrations fail loudly instead of accreting).

``observability/tpu-stack-alerts.yaml`` is additionally
schema-checked (dependency-free: pyyaml only) so a malformed rule
cannot ship — Prometheus would reject the whole rule file at load
time, silently disabling every alert in it.

Runs in tier-1 AND the CI ``router-loadbench`` job (no jax there:
engine/metrics.py imports only prometheus_client + the dataclass
modules).
"""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest
import yaml
from prometheus_client import CollectorRegistry

REPO = Path(__file__).resolve().parent.parent
DASHBOARD = REPO / "observability" / "tpu-stack-dashboard.json"
ALERTS = REPO / "observability" / "tpu-stack-alerts.yaml"
PROM_ADAPTER = REPO / "observability" / "prom-adapter.yaml"

# prefixes under the drift contract (vllm:* names are the reference
# stack's scrape contract, pinned by engine/router parity tests;
# router:* host gauges predate the contract)
PREFIX_RE = re.compile(r"\b(tpu(?:_router)?:[a-zA-Z0-9_]+)")

# registered families that are legitimately NOT charted or alerted on
# (each entry carries its why; additions need one too)
ORPHAN_ALLOWLIST = {
    # raw phase-decomposition histograms consumed via the aggregate
    # panels and the loadgen sample ring; receive/finalize are
    # sub-ms bookends charted indirectly through request_e2e
    "tpu_router:receive_seconds",
    "tpu_router:finalize_seconds",
    "tpu_router:request_e2e_seconds",
    # outcome counter behind the error-rate panels (errors/retries
    # are charted; the ok-outcome denominator is debug surface)
    "tpu_router:requests",
    # restore volume rides the charted kv_restore_seconds histogram +
    # fallback counter
    "tpu:kv_restore_blocks",
    # long-prefill requests + fallbacks are charted; per-chunk counts
    # are /debug/requests-granularity detail
    "tpu:long_prefill_chunks",
}


def _registered_families() -> dict[str, str]:
    """name -> metric type for every tpu:/tpu_router: family
    registered by the two metric modules."""
    from production_stack_tpu.engine.metrics import EngineMetrics
    from production_stack_tpu.router.services.metrics_service import (
        ROUTER_REGISTRY,
    )

    fams: dict[str, str] = {}
    engine_reg = CollectorRegistry()
    EngineMetrics("drift-gate", registry=engine_reg)
    for reg in (engine_reg, ROUTER_REGISTRY):
        for metric in reg.collect():
            if metric.name.startswith(("tpu:", "tpu_router:")):
                fams[metric.name] = metric.type
    return fams


def _queryable_names(families: dict[str, str]) -> set[str]:
    """The series names Prometheus actually stores for each family —
    what an expr may legally reference."""
    out: set[str] = set()
    for name, kind in families.items():
        if kind == "counter":
            out.add(f"{name}_total")
        elif kind == "histogram":
            out.update((f"{name}_bucket", f"{name}_count",
                        f"{name}_sum"))
        elif kind == "summary":
            out.update((f"{name}_count", f"{name}_sum"))
        else:  # gauge / unknown
            out.add(name)
    return out


def _dashboard_exprs() -> list[str]:
    dash = json.loads(DASHBOARD.read_text())
    exprs = []

    def walk(node):
        if isinstance(node, dict):
            expr = node.get("expr")
            if isinstance(expr, str):
                exprs.append(expr)
            for v in node.values():
                walk(v)
        elif isinstance(node, list):
            for v in node:
                walk(v)

    walk(dash)
    assert exprs, "dashboard has no panel exprs — parse failure?"
    return exprs


def _alert_exprs() -> list[str]:
    doc = yaml.safe_load(ALERTS.read_text())
    return [
        str(rule["expr"])
        for group in doc["groups"]
        for rule in group["rules"]
    ]


def _referenced(texts) -> set[str]:
    out: set[str] = set()
    for text in texts:
        out.update(PREFIX_RE.findall(text))
    return out


# -- direction 1: every referenced name is queryable from code ---------------
def test_dashboard_metrics_exist_in_code():
    queryable = _queryable_names(_registered_families())
    missing = sorted(_referenced(_dashboard_exprs()) - queryable)
    assert not missing, (
        "dashboard panels query series no registered metric exports "
        f"(stale name, or a counter queried without _total): {missing}"
    )


def test_alert_metrics_exist_in_code():
    queryable = _queryable_names(_registered_families())
    # recording rules mint new names (tpu_router:foo:rate5m) — they
    # are queryable by later rules in the same file
    doc = yaml.safe_load(ALERTS.read_text())
    recorded = {
        str(rule["record"])
        for group in doc["groups"]
        for rule in group["rules"]
        if "record" in rule
    }
    missing = sorted(
        _referenced(_alert_exprs()) - queryable - recorded
    )
    assert not missing, (
        f"alert/recording rules query unregistered series: {missing}"
    )


def test_prom_adapter_metrics_exist_in_code():
    queryable = _queryable_names(_registered_families())
    doc = yaml.safe_load(PROM_ADAPTER.read_text())
    rules = doc["rules"]["custom"]
    assert rules, "prom-adapter has no custom rules"
    texts = [
        r["seriesQuery"] + " " + r["metricsQuery"] for r in rules
    ]
    missing = sorted(_referenced(texts) - queryable)
    assert not missing, (
        f"prom-adapter rules export unregistered series: {missing}"
    )
    # the fleet autoscale family the helm/KEDA layer consumes must
    # stay exported (ISSUE 15 acceptance): both the load score and the
    # replica hint ride the adapter
    adapter_refs = _referenced(texts)
    assert "tpu_router:fleet_load_score" in adapter_refs
    assert "tpu_router:fleet_desired_replicas_hint" in adapter_refs


# -- direction 2: every registered family is consumed somewhere --------------
def test_no_orphaned_registrations():
    families = _registered_families()
    consumed = _referenced(_dashboard_exprs() + _alert_exprs())
    orphans = sorted(
        name for name, kind in families.items()
        if name not in ORPHAN_ALLOWLIST
        and not ({name, f"{name}_total", f"{name}_bucket",
                  f"{name}_count", f"{name}_sum"} & consumed)
    )
    assert not orphans, (
        "registered but never charted/alerted (chart it, alert on "
        f"it, or allowlist it with a why): {orphans}"
    )
    stale_allow = sorted(
        name for name in ORPHAN_ALLOWLIST if name not in families
    )
    assert not stale_allow, (
        f"allowlist names no longer registered: {stale_allow}"
    )


# -- alert rule file schema (dependency-free) --------------------------------
def test_alert_rules_schema():
    """The shape Prometheus requires: groups[].name + rules[], each
    rule EITHER a recording rule (record+expr, no for/annotations) OR
    an alert (alert+expr, optional for/labels/annotations). A
    malformed rule fails the whole file at Prometheus load time —
    this gate keeps that from shipping."""
    doc = yaml.safe_load(ALERTS.read_text())
    assert isinstance(doc, dict) and set(doc) == {"groups"}
    groups = doc["groups"]
    assert isinstance(groups, list) and groups
    seen_groups = set()
    seen_alerts = set()
    for group in groups:
        assert isinstance(group, dict)
        assert set(group) <= {"name", "interval", "rules"}
        name = group.get("name")
        assert isinstance(name, str) and name
        assert name not in seen_groups, f"duplicate group {name}"
        seen_groups.add(name)
        rules = group.get("rules")
        assert isinstance(rules, list) and rules, f"{name}: no rules"
        for rule in rules:
            assert isinstance(rule, dict), f"{name}: non-mapping rule"
            assert isinstance(rule.get("expr"), str) and rule["expr"], (
                f"{name}: rule without expr: {rule}"
            )
            if "record" in rule:
                assert set(rule) <= {"record", "expr", "labels"}, (
                    f"{name}: recording rule with alert-only keys: "
                    f"{rule}"
                )
                assert re.fullmatch(
                    r"[a-zA-Z_:][a-zA-Z0-9_:]*", rule["record"]
                ), f"{name}: invalid recorded name {rule['record']!r}"
            else:
                assert set(rule) <= {"alert", "expr", "for", "labels",
                                     "annotations"}, (
                    f"{name}: unknown alert keys in {rule}"
                )
                alert = rule.get("alert")
                assert isinstance(alert, str) and re.fullmatch(
                    r"[a-zA-Z_][a-zA-Z0-9_]*", alert
                ), f"{name}: invalid alert name {alert!r}"
                assert alert not in seen_alerts, (
                    f"duplicate alert {alert}"
                )
                seen_alerts.add(alert)
                if "for" in rule:
                    assert re.fullmatch(
                        r"\d+(ms|[smhdwy])", str(rule["for"])
                    ), f"{alert}: invalid for: {rule['for']!r}"
                for key in ("labels", "annotations"):
                    if key in rule:
                        assert isinstance(rule[key], dict) and all(
                            isinstance(v, str)
                            for v in rule[key].values()
                        ), f"{alert}: {key} must map to strings"
            # balanced parens/braces/brackets — the cheapest structural
            # promql sanity that catches truncated exprs
            expr = rule["expr"]
            for open_c, close_c in ("()", "{}", "[]"):
                assert expr.count(open_c) == expr.count(close_c), (
                    f"unbalanced {open_c}{close_c} in expr: {expr}"
                )


def test_alerts_cover_the_contracted_conditions():
    """The ISSUE 15 rule inventory: SLO burn fast/slow pair, admission
    shed spike, fleet asleep, shared-cache fallback movement, and
    scrape staleness must each have an alert — removing one is a
    contract change, not a cleanup."""
    doc = yaml.safe_load(ALERTS.read_text())
    alerts = {
        rule["alert"]: rule
        for group in doc["groups"]
        for rule in group["rules"]
        if "alert" in rule
    }
    for needed in ("SLOFastBurn", "SLOSlowBurn", "AdmissionShedSpike",
                   "FleetAsleep", "SharedCacheFallbacks",
                   "EngineScrapeStale"):
        assert needed in alerts, f"missing contracted alert {needed}"
    # the burn-rate pair reads BOTH windows (multi-window alerting:
    # a fast spike alone must not page after it has passed)
    for name in ("SLOFastBurn", "SLOSlowBurn"):
        expr = alerts[name]["expr"]
        assert 'window="fast"' in expr and 'window="slow"' in expr, (
            f"{name} must gate on both burn windows: {expr}"
        )


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
