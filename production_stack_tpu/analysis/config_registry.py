"""The single source of truth for the configuration contract.

Modeled on :mod:`production_stack_tpu.obs.metric_registry`: every router
CLI flag and every engine :class:`EngineConfig` field is declared ONCE
here, naming where it surfaces — the helm values path, the schema entry,
the template that emits it, and the docs file carrying its flag-table
row. The ``config-contract`` pstlint check verifies all five surfaces
agree in both directions:

- a parser flag with no :class:`ConfigSpec` is an undeclared knob;
- a spec with no parser flag is stale;
- a ``helm``-scoped flag must exist in ``helm/values.yaml`` AND
  ``helm/values.schema.json`` AND be emitted by its template AND match
  the parser default (unless ``default_differs`` documents why not);
- a ``cli-only`` flag must NOT be emitted by any template (emission
  means it grew a helm surface and must be reclassified);
- every ``routerSpec.*`` values/schema leaf must be claimed by a spec or
  by :data:`ROUTER_HELM_NON_FLAG` — a helm knob no flag consumes is
  exactly the "configured in values.yaml, silently ignored by the pod"
  drift class this registry exists to kill.

Kept importable with zero third-party dependencies so the analyzer and
CI consume it on a bare checkout. Scope values:

- ``helm``: user-settable values knob, wired through a template.
- ``template``: emitted by a template with a fixed or derived value
  (``$(POD_NAME)``, rendered service URLs) — no user values knob.
- ``cli-only``: no helm surface by design; reachable via
  ``routerSpec.extraArgs`` when needed. ``note`` says why.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

HELM = "helm"
TEMPLATE = "template"
CLI_ONLY = "cli-only"

ROUTER_TEMPLATE = "helm/templates/deployment-router.yaml"
ENGINE_TEMPLATE = "helm/templates/deployment-engine.yaml"

_ROUTER_DOC = "docs/router.md"
_RESILIENCE_DOC = "docs/resilience.md"
_HA_DOC = "docs/router-ha.md"


@dataclasses.dataclass(frozen=True)
class ConfigSpec:
    """One router CLI flag's contract across the five surfaces."""

    flag: str
    scope: str = HELM
    helm: Optional[str] = None        # values.yaml path (scope=helm)
    template: Optional[str] = None    # template emitting the flag
    doc: str = _ROUTER_DOC            # docs file with the flag row
    # Reason the parser default and the values.yaml default differ on
    # purpose (empty = they must match).
    default_differs: str = ""
    # Why there is no helm knob (scope=cli-only) / how the template
    # derives the value (scope=template).
    note: str = ""
    # Negation alias (--no-*): checked for parser existence + template
    # emission only; the positive twin carries the helm contract.
    negation_of: Optional[str] = None
    # String the template actually emits when it differs from ``flag``
    # (default-on booleans are rendered via their --no-* twin).
    emit: Optional[str] = None


def _helm(
    flag: str,
    path: str,
    doc: str = _ROUTER_DOC,
    default_differs: str = "",
) -> ConfigSpec:
    return ConfigSpec(
        flag, HELM, helm=path, template=ROUTER_TEMPLATE, doc=doc,
        default_differs=default_differs,
    )


def _tpl(flag: str, note: str, doc: str = _ROUTER_DOC) -> ConfigSpec:
    return ConfigSpec(
        flag, TEMPLATE, template=ROUTER_TEMPLATE, doc=doc, note=note
    )


def _cli(flag: str, note: str, doc: str = _ROUTER_DOC) -> ConfigSpec:
    return ConfigSpec(flag, CLI_ONLY, doc=doc, note=note)


# One entry per ``add_argument`` call in router/parser.py, same order.
ROUTER_FLAGS: Tuple[ConfigSpec, ...] = (
    _cli("--config", "bootstrap defaults file; helm renders flags directly"),
    _tpl("--host", "always 0.0.0.0 in a pod"),
    _helm("--port", "routerSpec.containerPort",
          default_differs="chart standardizes every pod port at 8000; "
          "bare CLI keeps 8001 to coexist with a local engine"),
    _helm("--service-discovery", "routerSpec.serviceDiscovery",
          default_differs="the chart is k8s-native (discovery=k8s); bare "
          "CLI defaults to static for local runs"),
    _cli("--k8s-service-discovery-type",
         "pod-ip is right inside the chart's own Service mesh; "
         "service-name mode is an extraArgs escape hatch"),
    _helm("--static-backends", "routerSpec.staticBackends"),
    _helm("--static-models", "routerSpec.staticModels"),
    _cli("--static-aliases", "static discovery detail; extraArgs"),
    _cli("--static-model-labels", "static discovery detail; extraArgs"),
    _cli("--static-model-types", "static discovery detail; extraArgs"),
    _cli("--static-pools", "static discovery detail; extraArgs — helm "
         "fleets declare disagg pools via servingEngineSpec.modelSpec[]."
         "pool, surfaced as the pst-pool pod label (docs/disagg.md)"),
    _cli("--static-backend-health-checks",
         "k8s discovery has readiness probes; static probing is extraArgs"),
    _cli("--health-check-interval", "companion of static health checks"),
    _tpl("--k8s-namespace", "rendered from .Release.Namespace"),
    _cli("--k8s-port", "chart engines always listen on 8000 (the default)"),
    _helm("--k8s-label-selector", "routerSpec.k8sLabelSelector",
          default_differs="the chart pins its own release labels; bare "
          "CLI defaults to no selector (all pods)"),
    _helm("--routing-logic", "routerSpec.routingLogic"),
    _helm("--session-key", "routerSpec.sessionKey"),
    _helm("--kv-aware-threshold", "routerSpec.kvAwareThreshold"),
    _helm("--fleet-eviction-ratio", "routerSpec.fleet.evictionRatio"),
    _helm("--fleet-load-factor", "routerSpec.fleet.loadFactor"),
    _tpl("--cache-controller-url",
         "rendered kv-controller service URL when "
         "kvControllerSpec.enableController"),
    _cli("--tokenizer-name", "kvaware hashing detail; extraArgs"),
    _helm("--prefill-model-labels", "routerSpec.prefillModelLabels"),
    _helm("--decode-model-labels", "routerSpec.decodeModelLabels"),
    ConfigSpec("--disagg-overlap", HELM,
               helm="routerSpec.disagg.overlap",
               template=ROUTER_TEMPLATE, emit="--no-disagg-overlap",
               note="default-on: the template renders the negation when "
               "disagg.overlap is false"),
    ConfigSpec("--no-disagg-overlap", TEMPLATE, template=ROUTER_TEMPLATE,
               negation_of="--disagg-overlap",
               note="emitted when disagg.overlap is false"),
    _helm("--admission-rate", "routerSpec.resilience.admissionRate",
          doc=_RESILIENCE_DOC),
    _helm("--admission-burst", "routerSpec.resilience.admissionBurst",
          doc=_RESILIENCE_DOC),
    _helm("--admission-queue-size", "routerSpec.resilience.admissionQueueSize",
          doc=_RESILIENCE_DOC),
    _helm("--admission-queue-timeout",
          "routerSpec.resilience.admissionQueueTimeout", doc=_RESILIENCE_DOC),
    _helm("--proxy-retries", "routerSpec.resilience.proxyRetries",
          doc=_RESILIENCE_DOC),
    _helm("--retry-backoff", "routerSpec.resilience.retryBackoff",
          doc=_RESILIENCE_DOC),
    _helm("--proxy-connect-timeout",
          "routerSpec.resilience.proxyConnectTimeout", doc=_RESILIENCE_DOC),
    _helm("--proxy-read-timeout", "routerSpec.resilience.proxyReadTimeout",
          doc=_RESILIENCE_DOC),
    _helm("--breaker-failure-threshold",
          "routerSpec.resilience.breakerFailureThreshold",
          doc=_RESILIENCE_DOC),
    _helm("--breaker-recovery-time",
          "routerSpec.resilience.breakerRecoveryTime", doc=_RESILIENCE_DOC),
    _helm("--breaker-half-open-probes",
          "routerSpec.resilience.breakerHalfOpenProbes", doc=_RESILIENCE_DOC),
    _helm("--tenant-isolation", "routerSpec.tenancy.enabled"),
    _helm("--tenant-config", "routerSpec.tenancy.configFile"),
    _helm("--tenant-default-weight", "routerSpec.tenancy.defaultWeight"),
    _helm("--tenant-default-tier", "routerSpec.tenancy.defaultTier"),
    _cli("--tenant-header", "identity-header rename is a gateway-"
         "integration detail; extraArgs"),
    _helm("--default-deadline-ms", "routerSpec.resilience.defaultDeadlineMs",
          doc=_RESILIENCE_DOC),
    _helm("--hedge-enabled", "routerSpec.resilience.hedge.enabled",
          doc=_RESILIENCE_DOC),
    _helm("--hedge-delay-ms", "routerSpec.resilience.hedge.delayMs",
          doc=_RESILIENCE_DOC),
    _helm("--hedge-quantile", "routerSpec.resilience.hedge.quantile",
          doc=_RESILIENCE_DOC),
    _helm("--hedge-max-outstanding-ratio",
          "routerSpec.resilience.hedge.maxOutstandingRatio",
          doc=_RESILIENCE_DOC),
    _helm("--stream-resume", "routerSpec.resilience.streamResume.enabled",
          doc=_RESILIENCE_DOC),
    _helm("--stream-resume-max-legs",
          "routerSpec.resilience.streamResume.maxLegs", doc=_RESILIENCE_DOC),
    ConfigSpec("--tracing", HELM, helm="routerSpec.observability.tracing",
               template=ROUTER_TEMPLATE, emit="--no-tracing",
               note="default-on: the template renders the negation when "
               "observability.tracing is false"),
    ConfigSpec("--no-tracing", TEMPLATE, template=ROUTER_TEMPLATE,
               negation_of="--tracing",
               note="emitted when observability.tracing is false"),
    _helm("--debug-requests-buffer",
          "routerSpec.observability.debugRequestsBuffer"),
    _helm("--log-format", "routerSpec.observability.logFormat"),
    _helm("--slo-ttft-ms", "routerSpec.observability.sloTtftMs"),
    _helm("--canary-interval",
          "routerSpec.observability.canary.intervalSeconds",
          default_differs="CLI default 0 keeps probing off; the helm knob "
          "is gated on canary.enabled and then defaults to 15s"),
    _helm("--canary-timeout", "routerSpec.observability.canary.timeoutSeconds"),
    ConfigSpec("--capacity-signal", HELM,
               helm="routerSpec.observability.capacitySignal",
               template=ROUTER_TEMPLATE, emit="--no-capacity-signal",
               note="default-on: the template renders the negation when "
               "observability.capacitySignal is false"),
    ConfigSpec("--no-capacity-signal", TEMPLATE, template=ROUTER_TEMPLATE,
               negation_of="--capacity-signal",
               note="emitted when observability.capacitySignal is false"),
    _helm("--state-backend", "routerSpec.stateBackend.type", doc=_HA_DOC),
    _tpl("--state-peers",
         "rendered dns:// spec of the headless peer service", doc=_HA_DOC),
    _helm("--state-sync-interval",
          "routerSpec.stateBackend.syncIntervalSeconds", doc=_HA_DOC),
    _helm("--state-peer-timeout",
          "routerSpec.stateBackend.peerTimeoutSeconds", doc=_HA_DOC),
    _tpl("--state-replica-id", "rendered $(POD_NAME)", doc=_HA_DOC),
    _helm("--engine-stats-interval", "routerSpec.engineScrapeInterval"),
    _helm("--request-stats-window", "routerSpec.requestStatsWindow"),
    _cli("--log-stats", "human-readable stdout loop; operators use /metrics"),
    _cli("--log-stats-interval", "companion of --log-stats"),
    _cli("--enable-batch-api", "batch/files API needs a volume story the "
         "chart does not ship yet; extraArgs"),
    _cli("--batch-db-path", "companion of --enable-batch-api"),
    _cli("--file-storage-class", "companion of --enable-batch-api"),
    _cli("--file-storage-path", "companion of --enable-batch-api"),
    _cli("--batch-processor", "companion of --enable-batch-api"),
    _helm("--sentry-dsn", "routerSpec.sentryDsn"),
    _cli("--sentry-traces-sample-rate", "sentry tuning detail; extraArgs"),
    _cli("--sentry-profile-session-sample-rate",
         "sentry tuning detail; extraArgs"),
    _tpl("--dynamic-config-json",
         "/config/dynamic.json from the rendered ConfigMap when "
         "routerSpec.dynamicConfig is set"),
    _cli("--callbacks", "arbitrary-code hook; mount your own module and "
         "wire via extraArgs"),
    _cli("--request-rewriter", "experimental; extraArgs"),
    _cli("--feature-gates", "experimental features; extraArgs"),
    _cli("--pii-analyzer", "experimental (PIIDetection gate); extraArgs"),
    _cli("--pii-types", "experimental (PIIDetection gate); extraArgs"),
    _cli("--semantic-cache-model", "experimental (SemanticCache gate)"),
    _cli("--semantic-cache-dir", "experimental (SemanticCache gate)"),
    _cli("--semantic-cache-threshold", "experimental (SemanticCache gate)"),
    _cli("--semantic-cache-embedder", "experimental (SemanticCache gate)"),
    _cli("--semantic-cache-embed-model", "experimental (SemanticCache gate)"),
    _tpl("--api-key",
         "$(PST_API_KEY) from servingEngineSpec.apiKeySecret — the fleet "
         "shares one key, so the router enforces and forwards the same "
         "secret the engines check"),
    _cli("--log-level", "debug knob; extraArgs"),
)

# routerSpec.* values/schema keys that are deliberately NOT CLI flags
# (deployment shape, not router configuration). Prefix semantics: a key
# equal to an entry or nested under it is allowed.
ROUTER_HELM_NON_FLAG: Tuple[str, ...] = (
    "routerSpec.enableRouter",
    "routerSpec.replicaCount",
    "routerSpec.image",
    "routerSpec.serviceType",
    "routerSpec.servicePort",
    "routerSpec.resources",
    "routerSpec.extraArgs",
    "routerSpec.dynamicConfig",
    "routerSpec.hpa",
    "routerSpec.podDisruptionBudget",
    # Gate knob: enables canary probing; the flags it gates
    # (--canary-interval/--canary-timeout) carry their own specs.
    "routerSpec.observability.canary.enabled",
)


@dataclasses.dataclass(frozen=True)
class EngineFieldSpec:
    """One :class:`EngineConfig` field's contract.

    ``flag`` is the engine CLI option (None = embedded-only field with no
    CLI surface); ``helm`` the values path under the modelSpec example
    (None = cli-only). ``emit`` overrides the string searched for in the
    engine template when the emission differs from ``flag`` (negation
    flags, renamed options).
    """

    field: str
    flag: Optional[str]
    helm: Optional[str] = None
    emit: Optional[str] = None
    default_differs: str = ""
    note: str = ""


def _ms(path: str) -> str:
    return "servingEngineSpec.modelSpec[]." + path


_SIZED = ("the committed modelSpec is the sized 8B reference example, "
          "not the engine's neutral default")

# One entry per EngineConfig dataclass field, declaration order.
ENGINE_FIELDS: Tuple[EngineFieldSpec, ...] = (
    EngineFieldSpec("model", "--model", _ms("model"),
                    default_differs=_SIZED),
    EngineFieldSpec("tokenizer", "--tokenizer",
                    note="defaults to the model directory"),
    EngineFieldSpec("served_model_name", "--served-model-name",
                    _ms("servedModelName"), default_differs=_SIZED),
    EngineFieldSpec("max_model_len", "--max-model-len",
                    _ms("engineConfig.maxModelLen"), default_differs=_SIZED),
    EngineFieldSpec("block_size", "--block-size",
                    _ms("engineConfig.blockSize")),
    EngineFieldSpec("num_kv_blocks", "--num-kv-blocks",
                    note="sized from the HBM budget by default"),
    EngineFieldSpec("hbm_utilization", "--gpu-memory-utilization",
                    _ms("engineConfig.hbmUtilization")),
    EngineFieldSpec("max_num_seqs", "--max-num-seqs",
                    _ms("engineConfig.maxNumSeqs")),
    EngineFieldSpec("max_prefill_tokens", "--max-num-batched-tokens",
                    _ms("engineConfig.maxNumBatchedTokens")),
    EngineFieldSpec("tensor_parallel_size", "--tensor-parallel-size",
                    _ms("engineConfig.tensorParallelSize"),
                    default_differs=_SIZED),
    EngineFieldSpec("data_parallel_size", "--data-parallel-size",
                    _ms("engineConfig.dataParallelSize")),
    EngineFieldSpec("pipeline_parallel_size", "--pipeline-parallel-size",
                    _ms("engineConfig.pipelineParallelSize")),
    EngineFieldSpec("sequence_parallel_size", "--sequence-parallel-size",
                    _ms("engineConfig.sequenceParallelSize")),
    EngineFieldSpec("expert_parallel_size", "--expert-parallel-size",
                    _ms("engineConfig.expertParallelSize")),
    EngineFieldSpec("kv_cache_dtype", "--kv-cache-dtype",
                    _ms("engineConfig.kvCacheDtype")),
    EngineFieldSpec("quantization", "--quantization",
                    _ms("engineConfig.quantization")),
    EngineFieldSpec("attn_impl", "--attn-impl",
                    _ms("engineConfig.attnImpl"),
                    default_differs="the chart targets TPU node pools "
                    "(pallas); the engine's neutral default is auto"),
    EngineFieldSpec("moe_impl", "--moe-impl",
                    note="MoE kernel selection; extraArgs"),
    EngineFieldSpec("enable_prefix_caching", "--enable-prefix-caching",
                    _ms("engineConfig.enablePrefixCaching"),
                    emit="--no-enable-prefix-caching"),
    EngineFieldSpec("num_decode_steps", "--num-decode-steps",
                    _ms("engineConfig.numDecodeSteps"),
                    default_differs=_SIZED),
    EngineFieldSpec("min_decode_bucket", "--min-decode-bucket",
                    note="lattice floor tuning; extraArgs"),
    EngineFieldSpec("speculative_ngram", "--speculative-ngram",
                    note="speculation is opt-in via extraArgs"),
    EngineFieldSpec("speculative_mtp", "--speculative-mtp",
                    note="a model's own draft is opt-in via extraArgs"),
    EngineFieldSpec("ngram_min", "--ngram-min",
                    note="companion of --speculative-ngram"),
    EngineFieldSpec("ngram_max", "--ngram-max",
                    note="companion of --speculative-ngram"),
    EngineFieldSpec("ngram_lookback", "--ngram-lookback",
                    note="companion of --speculative-ngram"),
    EngineFieldSpec("overlap_decode", "--overlap-decode",
                    note="default-on; --no-overlap-decode is the CLI "
                    "escape hatch"),
    EngineFieldSpec("seed", "--seed", note="debug determinism; extraArgs"),
    EngineFieldSpec("cpu_offload_blocks", "--cpu-offload-blocks",
                    _ms("kvCache.cpuOffloadBlocks"),
                    default_differs="the chart provisions a host-DRAM "
                    "page pool; the engine default is off"),
    EngineFieldSpec("remote_kv_url", "--remote-kv-url",
                    note="rendered cache-server URL when "
                    "kvCache.useRemoteStore (template-derived)"),
    EngineFieldSpec("cache_controller_url", "--cache-controller-url",
                    note="rendered kv-controller URL when "
                    "kvControllerSpec.enableController (template-derived)"),
    EngineFieldSpec("engine_url", "--engine-url",
                    note="self-URL for controller reports; the pod "
                    "derives it from $(POD_IP)"),
    EngineFieldSpec("enable_lora", "--enable-lora",
                    _ms("lora.enabled"),
                    default_differs="gated emission: the flag only "
                    "renders when lora.enabled"),
    EngineFieldSpec("max_loras", "--max-loras",
                    note="LoRA capacity tuning; extraArgs"),
    EngineFieldSpec("max_lora_rank", "--max-lora-rank",
                    note="LoRA capacity tuning; extraArgs"),
    EngineFieldSpec("lora_dir", "--lora-dir", _ms("lora.adapterDir"),
                    default_differs="gated emission with the chart's "
                    "shared adapter volume path"),
    EngineFieldSpec("kv_swap", "--kv-swap", _ms("engineConfig.kvSwap"),
                    emit="--no-kv-swap"),
    EngineFieldSpec("swap_quantum_tokens", "--swap-quantum-tokens",
                    _ms("engineConfig.swapQuantumTokens")),
    EngineFieldSpec("swap_stash_blocks", "--swap-stash-blocks",
                    _ms("engineConfig.swapStashBlocks")),
    EngineFieldSpec("kv_role", "--kv-role", _ms("kvCache.kvRole")),
    EngineFieldSpec("kv_prefetch_depth", "--kv-prefetch-depth",
                    _ms("kvCache.kvPrefetchDepth")),
    EngineFieldSpec("kv_transfer_timeout_s", "--kv-transfer-timeout-s",
                    _ms("kvCache.kvTransferTimeoutS")),
    EngineFieldSpec("kv_replication", "--kv-replication",
                    _ms("kvCache.kvReplication")),
    EngineFieldSpec("deadline_shedding", "--deadline-shedding",
                    "servingEngineSpec.deadlineShedding",
                    emit="--no-deadline-shedding"),
    EngineFieldSpec("tenant_fairness", "--tenant-fairness",
                    "servingEngineSpec.tenantFairness",
                    emit="--no-tenant-fairness"),
    EngineFieldSpec("warmup", "--warmup", "servingEngineSpec.warmup.mode",
                    default_differs="helm deploys warmed (full); bare CLI "
                    "and embedded runs default to off so dev loops stay "
                    "instant"),
    EngineFieldSpec("warmup_bucket_budget", "--warmup-bucket-budget",
                    "servingEngineSpec.warmup.bucketBudget"),
    EngineFieldSpec("compile_cache_dir", "--compile-cache-dir",
                    "servingEngineSpec.warmup.cacheDir"),
    EngineFieldSpec("flight_buffer", "--flight-buffer",
                    "servingEngineSpec.observability.flightBuffer"),
    EngineFieldSpec("flight_snapshot_dir", "--flight-snapshot-dir",
                    "servingEngineSpec.observability.flightSnapshotDir"),
    EngineFieldSpec("cost_attribution", "--cost-attribution",
                    "servingEngineSpec.observability.costAttribution",
                    emit="--no-cost-attribution"),
)

@dataclasses.dataclass(frozen=True)
class AutoscaleKeySpec:
    """One ``spec.autoscale.<key>`` TPURuntime knob's contract.

    The autoscale knobs live in the CRD, not in helm (the chart does
    not render TPURuntime CRs — per-pool policy is declarative), so
    their four surfaces are: the CRD openAPI schema
    (:data:`OPERATOR_CRD`), the C++ reconciler that consumes them
    (:data:`OPERATOR_RECONCILERS` reads ``as.at("<key>")``), the
    committed sample CR (:data:`OPERATOR_SAMPLE`), and the docs page
    (:data:`AUTOSCALE_DOC`). The config-contract check proves all four
    in both directions — a CRD key no reconciler reads is
    configuration theater, a reconciler read the CRD does not declare
    is an undocumented knob.
    """

    key: str
    note: str = ""


OPERATOR_CRD = "operator/crds/crds.yaml"
OPERATOR_RECONCILERS = "operator/src/reconcilers.cc"
OPERATOR_SAMPLE = "operator/config/samples/tpuruntime.yaml"
AUTOSCALE_DOC = "docs/autoscaling.md"

AUTOSCALE_KEYS: Tuple[AutoscaleKeySpec, ...] = (
    AutoscaleKeySpec("minReplicas", "floor; 0 allowed with scaleToZero"),
    AutoscaleKeySpec("maxReplicas", "ceiling, clamps any replica hint"),
    AutoscaleKeySpec("scaleDownStabilizationS",
                     "cooldown after any scale event"),
    AutoscaleKeySpec("drainDeadlineS",
                     "blocking-drain bound per scale-down victim"),
    AutoscaleKeySpec("idleVerdicts",
                     "consecutive idle passes arming the shrink paths"),
    AutoscaleKeySpec("scaleToZero",
                     "park a single slept standby at sustained idle"),
)

ROUTER_BY_FLAG: Dict[str, ConfigSpec] = {s.flag: s for s in ROUTER_FLAGS}
ENGINE_BY_FIELD: Dict[str, EngineFieldSpec] = {
    s.field: s for s in ENGINE_FIELDS
}
