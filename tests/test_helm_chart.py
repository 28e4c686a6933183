"""Chart packaging tests (reference: helm lint + functionality-helm-chart CI).

Without a cluster (or even a helm binary) these validate the layers that
break most often: the values schema against every shipped values file, the
Go-template structure of each template, and — when `helm` is on PATH — a
full `helm template` render of the default, multihost, and disagg example
values (the reference's chart-testing analogue).
"""

import json
import re
import shutil
import subprocess
from pathlib import Path

import pytest
import yaml

HELM_DIR = Path(__file__).resolve().parent.parent / "helm"
DOCKER_DIR = Path(__file__).resolve().parent.parent / "docker"


def _load_values(path):
    with open(path) as f:
        return yaml.safe_load(f)


def test_values_schema_is_valid_jsonschema():
    import jsonschema

    with open(HELM_DIR / "values.schema.json") as f:
        schema = json.load(f)
    jsonschema.Draft7Validator.check_schema(schema)


@pytest.mark.parametrize(
    "values_file",
    ["values.yaml"] + [f"examples/{p.name}" for p in sorted(
        (HELM_DIR / "examples").glob("*.yaml"))],
)
def test_values_files_validate_against_schema(values_file):
    import jsonschema

    with open(HELM_DIR / "values.schema.json") as f:
        schema = json.load(f)
    jsonschema.validate(_load_values(HELM_DIR / values_file), schema)


def test_engine_template_readiness_probe_targets_ready():
    """The engine deployment's readinessProbe must hit /ready (warmup
    gated), while startup/liveness stay on /health — a warming engine is
    alive but must leave the Service until precompilation finishes."""
    text = (HELM_DIR / "templates" / "deployment-engine.yaml").read_text()
    assert "readinessProbe" in text
    assert "path: /ready" in text
    # Liveness must NOT move to /ready: a long precompile would get the
    # pod killed mid-warmup.
    liveness = text.split("livenessProbe", 1)[1].split("readinessProbe")[0]
    assert "/health" in liveness


def test_engine_template_wires_warmup_flags_and_cache_volume():
    text = (HELM_DIR / "templates" / "deployment-engine.yaml").read_text()
    assert '"--warmup"' in text
    assert '"--warmup-bucket-budget"' in text
    assert '"--compile-cache-dir"' in text
    # Cache volume supports both persistence shapes.
    assert "compile-cache" in text
    assert "cachePVC" in text.replace("$warmup.cachePVC", "cachePVC")
    assert "hostPath" in text
    # A cacheDir with no backing mount must fail the render loudly, not
    # silently write the "persistent" cache to the container overlay FS.
    assert 'fail "servingEngineSpec.warmup.cacheDir is set but neither' in text


@pytest.mark.parametrize("flag,value", [
    ("--speculative-ngram", "4"), ("--speculative-mtp", "1")])
def test_engine_template_passes_a_draft_flag_through_extra_args(flag, value):
    """Neither draft source has a value of its own in the chart: both ride
    ``engineConfig.extraArgs``, which the template hands the engine as they
    are; the values file names both, and the engine's parser takes them."""
    from production_stack_tpu.engine.server import parse_engine_args

    text = (HELM_DIR / "templates" / "deployment-engine.yaml").read_text()
    assert re.search(r"range \.extraArgs \}\}\s*- \{\{ \. \| quote \}\}", text)
    assert f'"{flag}"' in (HELM_DIR / "values.yaml").read_text()
    args = parse_engine_args(["--model", "m", flag, value])
    assert getattr(args, flag[2:].replace("-", "_")) == int(value)


def test_values_schema_covers_warmup():
    with open(HELM_DIR / "values.schema.json") as f:
        schema = json.load(f)
    warmup = schema["properties"]["servingEngineSpec"]["properties"]["warmup"]
    props = warmup["properties"]
    assert set(props) == {
        "mode", "bucketBudget", "cacheDir", "cachePVC", "cacheHostPath"
    }
    assert props["mode"]["enum"] == ["full", "lazy", "off"]

    import jsonschema

    # Defaults ship warmup on.
    values = _load_values(HELM_DIR / "values.yaml")
    assert values["servingEngineSpec"]["warmup"]["mode"] == "full"
    # An invalid mode must be rejected, not silently templated.
    bad = dict(values)
    bad["servingEngineSpec"] = dict(values["servingEngineSpec"])
    bad["servingEngineSpec"]["warmup"] = {"mode": "sometimes"}
    with pytest.raises(jsonschema.ValidationError):
        jsonschema.validate(bad, schema)


def test_router_replicas_gated_on_shared_state_backend():
    """replicaCount > 1 with the in-memory backend must fail the render
    loudly (divergent routing state), both at the schema layer and in the
    template itself; with the gossip backend it must validate."""
    import jsonschema

    with open(HELM_DIR / "values.schema.json") as f:
        schema = json.load(f)
    values = _load_values(HELM_DIR / "values.yaml")

    def with_router(**overrides):
        v = dict(values)
        v["routerSpec"] = {**values["routerSpec"], **overrides}
        return v

    # Schema: 2 replicas + memory backend rejected...
    with pytest.raises(jsonschema.ValidationError):
        jsonschema.validate(
            with_router(replicaCount=2, stateBackend={"type": "memory"}),
            schema,
        )
    # ... and 2 replicas + gossip accepted.
    jsonschema.validate(
        with_router(replicaCount=2, stateBackend={"type": "gossip"}), schema
    )
    # Defaults stay single-replica + memory (zero behavior change).
    assert values["routerSpec"]["replicaCount"] == 1
    assert values["routerSpec"]["stateBackend"]["type"] == "memory"

    # Template: the same invariant enforced at render time for operators
    # who bypass schema validation.
    text = (HELM_DIR / "templates" / "deployment-router.yaml").read_text()
    assert 'fail "routerSpec.replicaCount > 1 requires' in text
    # Gossip wiring: peers via the headless service, stable replica ids.
    assert "--state-peers" in text
    assert "router-headless" in text
    assert "publishNotReadyAddresses: true" in text
    assert "$(POD_NAME)" in text


def test_router_template_has_pdb_and_ready_probe():
    text = (HELM_DIR / "templates" / "deployment-router.yaml").read_text()
    assert "PodDisruptionBudget" in text
    assert "minAvailable" in text
    # Readiness must hit /ready (state-sync + drain gated); liveness and
    # startup stay on /health — an unsynced replica is alive, not broken.
    assert "readinessProbe" in text
    ready_block = text.split("readinessProbe", 1)[1].split("startupProbe")[0]
    assert "path: /ready" in ready_block
    liveness = text.split("livenessProbe", 1)[1].split("readinessProbe")[0]
    assert "/health" in liveness
    # Rolling restarts drain the replica (journals pushed to survivors).
    assert "/router/drain" in text


def test_templates_have_balanced_go_template_delimiters():
    for tpl in sorted((HELM_DIR / "templates").glob("*")):
        text = tpl.read_text()
        assert text.count("{{") == text.count("}}"), tpl.name
        # if/range/with must close with end.
        opens = len(re.findall(r"{{-?\s*(if|range|with|define)\b", text))
        ends = len(re.findall(r"{{-?\s*end\s*-?}}", text))
        assert opens == ends, f"{tpl.name}: {opens} blocks vs {ends} ends"


def test_dockerfiles_cover_every_component():
    # engine + kvserver/controller share one image; router, operator+picker,
    # LoRA sidecar each get their own (reference docker/ has 3 files).
    for name in ["Dockerfile", "Dockerfile.router", "Dockerfile.operator",
                 "Dockerfile.sidecar"]:
        path = DOCKER_DIR / name
        assert path.exists(), name
        text = path.read_text()
        assert text.startswith("#"), f"{name} missing header comment"
        assert "FROM" in text
    # Entry points the chart relies on must exist in pyproject.
    pyproject = (DOCKER_DIR.parent / "pyproject.toml").read_text()
    for script in ["pst-engine", "pst-router", "pst-kv-server",
                   "pst-kv-controller"]:
        assert script in pyproject, script
    # The sidecar's script must ship.
    assert (DOCKER_DIR.parent / "scripts" / "adapter_downloader.py").exists()


HELM = shutil.which("helm")


@pytest.mark.skipif(HELM is None, reason="helm binary not on PATH")
@pytest.mark.parametrize(
    "values_file",
    [None, "examples/values-minimal.yaml", "examples/values-multihost.yaml",
     "examples/values-disagg.yaml"],
)
def test_helm_template_renders(values_file):
    cmd = [HELM, "template", "pst", str(HELM_DIR)]
    if values_file:
        cmd += ["-f", str(HELM_DIR / values_file)]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    docs = [d for d in yaml.safe_load_all(proc.stdout) if d]
    kinds = {d["kind"] for d in docs}
    assert "Deployment" in kinds or "LeaderWorkerSet" in kinds
    assert "Service" in kinds
