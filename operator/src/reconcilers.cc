#include "reconcilers.hpp"

#include <algorithm>
#include <climits>
#include <cstdio>
#include <ctime>

namespace pst {

namespace {

constexpr const char* kHashAnnotation = "pst.production-stack.io/spec-hash";

Json owner_ref(const Json& cr) {
  Json ref = Json::object();
  ref["apiVersion"] = cr.at("apiVersion").as_string_or(
      "pst.production-stack.io/v1alpha1");
  ref["kind"] = cr.at("kind").as_string();
  ref["name"] = cr.at({"metadata", "name"}).as_string();
  ref["uid"] = cr.at({"metadata", "uid"}).as_string_or("");
  ref["controller"] = true;
  ref["blockOwnerDeletion"] = true;
  Json arr = Json::array();
  arr.push_back(ref);
  return arr;
}

Json meta_for(const Json& cr, const std::string& name, const std::string& ns,
              const std::string& component) {
  Json m = Json::object();
  m["name"] = name;
  m["namespace"] = ns;
  Json labels = Json::object();
  labels["app.kubernetes.io/part-of"] = "production-stack-tpu";
  labels["app.kubernetes.io/component"] = component;
  labels["app"] = name;
  labels["environment"] = "production-stack-tpu";
  if (component == "engine")
    labels["model"] = cr.at({"metadata", "name"}).as_string();
  m["labels"] = labels;
  Json ann = Json::object();
  ann[kHashAnnotation] = spec_hash(cr.at("spec"));
  m["annotations"] = ann;
  m["ownerReferences"] = owner_ref(cr);
  return m;
}

void push_arg(Json& args, const std::string& flag, const std::string& value) {
  args.push_back(flag);
  args.push_back(value);
}

void push_arg_num(Json& args, const std::string& flag, long value) {
  push_arg(args, flag, std::to_string(value));
}

std::string now_rfc3339() {
  char buf[32];
  time_t t = time(nullptr);
  struct tm tm_utc;
  gmtime_r(&t, &tm_utc);
  strftime(buf, sizeof(buf), "%Y-%m-%dT%H:%M:%SZ", &tm_utc);
  return buf;
}

// Generic "ensure object matches CR spec" upsert keyed on the spec-hash
// annotation (drift detection without semantic diffing).
bool upsert(const K8sClient& k8s, const std::string& api,
            const std::string& plural, const Json& desired) {
  const std::string name = desired.at({"metadata", "name"}).as_string();
  auto existing = k8s.get(api, plural, name);
  if (!existing) {
    k8s.create(api, plural, desired);
    return true;
  }
  const std::string want =
      desired.at({"metadata", "annotations"}).at(kHashAnnotation).as_string();
  const std::string have = existing->at({"metadata", "annotations"})
                               .at(kHashAnnotation)
                               .as_string();
  if (want != have) {
    Json replacement = desired;
    // Carry resourceVersion for optimistic concurrency on PUT.
    const std::string rv =
        existing->at({"metadata", "resourceVersion"}).as_string();
    if (!rv.empty()) replacement["metadata"]["resourceVersion"] = rv;
    k8s.replace(api, plural, name, replacement);
    return true;
  }
  return false;
}

}  // namespace

std::string spec_hash(const Json& spec) {
  // FNV-1a over the canonical dump (std::map keys are sorted → stable).
  const std::string s = spec.dump();
  uint64_t h = 1469598103934665603ull;
  for (unsigned char c : s) {
    h ^= c;
    h *= 1099511628211ull;
  }
  char buf[20];
  snprintf(buf, sizeof(buf), "%016llx", static_cast<unsigned long long>(h));
  return buf;
}

// ---------------------------------------------------------------------------
// TPURuntime
// ---------------------------------------------------------------------------

Json build_engine_deployment(const Json& cr, const std::string& ns) {
  const Json& spec = cr.at("spec");
  const std::string cr_name = cr.at({"metadata", "name"}).as_string();
  const std::string name = cr_name + "-engine";

  Json args = Json::array();
  push_arg(args, "--model", spec.at("model").as_string_or("tiny-llama-debug"));
  if (spec.has("servedModelName"))
    push_arg(args, "--served-model-name", spec.at("servedModelName").as_string());
  push_arg(args, "--host", "0.0.0.0");
  push_arg_num(args, "--port", 8000);
  const Json& ec = spec.at("engineConfig");
  push_arg_num(args, "--max-model-len", ec.at("maxModelLen").as_int(4096));
  push_arg_num(args, "--max-num-seqs", ec.at("maxNumSeqs").as_int(64));
  push_arg_num(args, "--max-num-batched-tokens",
               ec.at("maxNumBatchedTokens").as_int(2048));
  push_arg_num(args, "--tensor-parallel-size",
               ec.at("tensorParallelSize").as_int(1));
  push_arg_num(args, "--block-size", ec.at("blockSize").as_int(32));
  push_arg(args, "--attn-impl", ec.at("attnImpl").as_string_or("auto"));
  // Weight-only quantization (vllm serve --quantization analogue).
  if (ec.has("quantization") &&
      !ec.at("quantization").as_string_or("").empty())
    push_arg(args, "--quantization", ec.at("quantization").as_string_or(""));
  if (ec.has("numDecodeSteps") && ec.at("numDecodeSteps").as_int(0) > 0)
    push_arg_num(args, "--num-decode-steps", ec.at("numDecodeSteps").as_int());
  if (ec.has("hbmUtilization")) {
    char buf[16];
    snprintf(buf, sizeof(buf), "%.3f", ec.at("hbmUtilization").as_number(0.9));
    push_arg(args, "--gpu-memory-utilization", buf);
  }
  if (ec.has("enablePrefixCaching") && !ec.at("enablePrefixCaching").as_bool(true))
    args.push_back("--no-enable-prefix-caching");
  const Json& kv = spec.at("kvCache");
  if (kv.at("cpuOffloadBlocks").as_int(0) > 0)
    push_arg_num(args, "--cpu-offload-blocks", kv.at("cpuOffloadBlocks").as_int());
  if (kv.has("remoteKvUrl") && !kv.at("remoteKvUrl").as_string().empty())
    push_arg(args, "--remote-kv-url", kv.at("remoteKvUrl").as_string());
  if (kv.has("kvRole") && kv.at("kvRole").as_string_or("none") != "none")
    push_arg(args, "--kv-role", kv.at("kvRole").as_string());
  if (spec.has("cacheControllerUrl"))
    push_arg(args, "--cache-controller-url",
             spec.at("cacheControllerUrl").as_string());
  for (const auto& extra : ec.at("extraArgs").items()) args.push_back(extra);

  Json container = Json::object();
  container["name"] = "engine";
  container["image"] = spec.at("image").as_string_or(
      "ghcr.io/production-stack-tpu/engine:0.1.0");
  Json cmd = Json::array();
  cmd.push_back("pst-engine");
  container["command"] = cmd;
  container["args"] = args;
  Json port = Json::object();
  port["containerPort"] = 8000;
  port["name"] = "http";
  Json ports = Json::array();
  ports.push_back(port);
  container["ports"] = ports;

  Json resources = Json::object();
  Json requests = Json::object();
  requests["cpu"] = spec.at({"resources", "cpu"}).as_string_or("4");
  requests["memory"] = spec.at({"resources", "memory"}).as_string_or("16Gi");
  Json limits = Json::object();
  const long chips = spec.at({"tpu", "chips"}).as_int(0);
  if (chips > 0) {
    requests["google.com/tpu"] = std::to_string(chips);
    limits["google.com/tpu"] = std::to_string(chips);
  }
  resources["requests"] = requests;
  if (chips > 0) resources["limits"] = limits;
  container["resources"] = resources;

  Json probe = Json::object();
  Json http_get = Json::object();
  http_get["path"] = "/health";
  http_get["port"] = 8000;
  probe["httpGet"] = http_get;
  probe["periodSeconds"] = 10;
  probe["failureThreshold"] = 120;
  container["startupProbe"] = probe;
  Json live = probe;
  live["failureThreshold"] = 6;
  container["livenessProbe"] = live;

  Json pod_spec = Json::object();
  if (chips > 0) {
    Json node_selector = Json::object();
    node_selector["cloud.google.com/gke-tpu-accelerator"] =
        spec.at({"tpu", "accelerator"}).as_string_or("tpu-v5-lite-podslice");
    node_selector["cloud.google.com/gke-tpu-topology"] =
        spec.at({"tpu", "topology"}).as_string_or("2x4");
    pod_spec["nodeSelector"] = node_selector;
    Json tol = Json::object();
    tol["key"] = "google.com/tpu";
    tol["operator"] = "Exists";
    tol["effect"] = "NoSchedule";
    Json tols = Json::array();
    tols.push_back(tol);
    pod_spec["tolerations"] = tols;
  }
  if (spec.at({"storage", "enabled"}).as_bool(false)) {
    Json vm = Json::object();
    vm["name"] = "model-storage";
    vm["mountPath"] = "/data";
    Json vms = Json::array();
    vms.push_back(vm);
    container["volumeMounts"] = vms;
    Json vol = Json::object();
    vol["name"] = "model-storage";
    Json pvc_src = Json::object();
    pvc_src["claimName"] = cr_name + "-pvc";
    vol["persistentVolumeClaim"] = pvc_src;
    Json vols = Json::array();
    vols.push_back(vol);
    pod_spec["volumes"] = vols;
    Json env = Json::object();
    env["name"] = "HF_HOME";
    env["value"] = "/data";
    Json envs = Json::array();
    envs.push_back(env);
    container["env"] = envs;
  }
  Json containers = Json::array();
  containers.push_back(container);
  pod_spec["containers"] = containers;

  Json pod_meta = Json::object();
  Json pod_labels = Json::object();
  pod_labels["app"] = name;
  pod_labels["model"] = cr_name;
  pod_labels["environment"] = "production-stack-tpu";
  pod_meta["labels"] = pod_labels;

  Json tmpl = Json::object();
  tmpl["metadata"] = pod_meta;
  tmpl["spec"] = pod_spec;

  Json selector = Json::object();
  Json match = Json::object();
  match["app"] = name;
  selector["matchLabels"] = match;

  Json dspec = Json::object();
  long replicas = spec.at("replicas").as_int(1);
  if (spec.has("autoscale")) {
    // The actuator owns the replica count: a spec change (hash mismatch →
    // full replace) must carry the last ACTUATED scale forward, not reset
    // the fleet to spec.replicas mid-surge.
    replicas = cr.at({"status", "desiredReplicas"}).as_int(replicas);
  }
  dspec["replicas"] = replicas;
  dspec["selector"] = selector;
  dspec["template"] = tmpl;

  Json dep = Json::object();
  dep["apiVersion"] = "apps/v1";
  dep["kind"] = "Deployment";
  dep["metadata"] = meta_for(cr, name, ns, "engine");
  dep["spec"] = dspec;
  return dep;
}

Json build_engine_service(const Json& cr, const std::string& ns) {
  const std::string cr_name = cr.at({"metadata", "name"}).as_string();
  const std::string name = cr_name + "-engine";
  Json svc = Json::object();
  svc["apiVersion"] = "v1";
  svc["kind"] = "Service";
  svc["metadata"] = meta_for(cr, name, ns, "engine");
  Json sel = Json::object();
  sel["app"] = name;
  Json port = Json::object();
  port["port"] = 8000;
  port["targetPort"] = 8000;
  port["name"] = "http";
  Json ports = Json::array();
  ports.push_back(port);
  Json sspec = Json::object();
  sspec["selector"] = sel;
  sspec["ports"] = ports;
  svc["spec"] = sspec;
  return svc;
}

Json build_engine_pvc(const Json& cr, const std::string& ns) {
  const Json& st = cr.at({"spec", "storage"});
  Json pvc = Json::object();
  pvc["apiVersion"] = "v1";
  pvc["kind"] = "PersistentVolumeClaim";
  pvc["metadata"] =
      meta_for(cr, cr.at({"metadata", "name"}).as_string() + "-pvc", ns, "engine");
  Json pspec = Json::object();
  Json modes = Json::array();
  modes.push_back(st.at("accessMode").as_string_or("ReadWriteOnce"));
  pspec["accessModes"] = modes;
  if (st.has("storageClass") && !st.at("storageClass").as_string().empty())
    pspec["storageClassName"] = st.at("storageClass").as_string();
  Json req = Json::object();
  Json storage = Json::object();
  storage["storage"] = st.at("size").as_string_or("100Gi");
  req["requests"] = storage;
  pspec["resources"] = req;
  pvc["spec"] = pspec;
  return pvc;
}

// ---------------------------------------------------------------------------
// Autoscale actuator (docs/autoscaling.md "Reconcile semantics")
// ---------------------------------------------------------------------------

namespace {

struct EnginePod {
  std::string name;
  std::string base;  // http://ip:port
};

std::vector<EnginePod> ready_engine_pods(const K8sClient& k8s,
                                         const std::string& base_model) {
  std::vector<EnginePod> pods;
  Json list = k8s.list(kCoreV1, "pods", "model%3D" + base_model);
  for (const auto& pod : list.at("items").items()) {
    const std::string ip = pod.at({"status", "podIP"}).as_string();
    const std::string phase = pod.at({"status", "phase"}).as_string();
    if (ip.empty() || phase != "Running") continue;
    // Engine port from the pod's declared containerPort (default 8000).
    long port = 8000;
    const auto& containers = pod.at({"spec", "containers"}).items();
    if (!containers.empty()) {
      const auto& ports = containers[0].at("ports").items();
      if (!ports.empty()) port = ports[0].at("containerPort").as_int(8000);
    }
    pods.push_back({pod.at({"metadata", "name"}).as_string(),
                    "http://" + ip + ":" + std::to_string(port)});
  }
  std::sort(pods.begin(), pods.end(),
            [](const EnginePod& a, const EnginePod& b) { return a.name < b.name; });
  return pods;
}

// Consumer contract with the router's GET /autoscale/signal
// (production_stack_tpu/router/services/capacity.py compute_signal).
// tests/test_flight_cost.py regex-extracts this list and asserts every
// field exists in the Python producer's output, so a producer rename
// breaks the build's tests, not a running fleet. A signal response
// missing any of these is version skew and is discarded — the operator
// never actuates on partial evidence.
constexpr const char* kSignalFields[] = {
    "ts",
    "replica_hint",
    "queue_depth",
    "in_flight_total",
    "engines_ready",
    "page_burning",
    "saturation",
    "evidence_replicas",
};

bool signal_valid(const Json& sig) {
  for (const char* field : kSignalFields)
    if (!sig.has(field)) return false;
  return true;
}

// One router replica's worth of evidence, max-merged across replicas.
// Each replica's signal is already gossip-merged over the fleet (burn =
// max, queue = sum across router peers), so replicas converge on the SAME
// values within one sync interval — max here is anti-skew defense for the
// convergence window, not an aggregation step; summing would double-count.
struct SignalView {
  long hint = -1;  // -1 = no reachable router produced a valid signal
  long queue_depth = 0;
  long in_flight = 0;
  long routers = 0;  // replicas that answered with a valid signal
};

struct RouterReplica {
  std::string pod;
  std::string base;  // http://ip:port
};

std::vector<RouterReplica> router_replicas(const K8sClient& k8s) {
  // Router pods carry only {app: <name>-router}; the component label lives
  // on the Deployment/Service metadata. Walk component=router Services to
  // their selector, then to Running pods.
  std::vector<RouterReplica> out;
  Json svcs = k8s.list(kCoreV1, "services",
                       "app.kubernetes.io%2Fcomponent%3Drouter");
  for (const auto& svc : svcs.at("items").items()) {
    const std::string app = svc.at({"spec", "selector", "app"}).as_string();
    if (app.empty()) continue;
    long port = 8000;
    const auto& ports = svc.at({"spec", "ports"}).items();
    if (!ports.empty()) port = ports[0].at("targetPort").as_int(8000);
    Json pods = k8s.list(kCoreV1, "pods", "app%3D" + app);
    for (const auto& pod : pods.at("items").items()) {
      const std::string ip = pod.at({"status", "podIP"}).as_string();
      if (ip.empty()) continue;
      if (pod.at({"status", "phase"}).as_string() != "Running") continue;
      out.push_back({pod.at({"metadata", "name"}).as_string(),
                     "http://" + ip + ":" + std::to_string(port)});
    }
  }
  std::sort(out.begin(), out.end(),
            [](const RouterReplica& a, const RouterReplica& b) {
              return a.pod < b.pod;
            });
  return out;
}

SignalView poll_signal(const std::vector<RouterReplica>& routers) {
  SignalView v;
  for (const auto& r : routers) {
    try {
      auto resp = http_request("GET", r.base + "/autoscale/signal", "", "", 5);
      if (!resp.ok()) continue;
      Json sig = Json::parse(resp.body);
      if (!signal_valid(sig)) continue;
      v.routers++;
      v.hint = std::max(v.hint, sig.at("replica_hint").as_int(0));
      v.queue_depth = std::max(v.queue_depth, sig.at("queue_depth").as_int(0));
      v.in_flight = std::max(v.in_flight, sig.at("in_flight_total").as_int(0));
    } catch (...) {
      // Unreachable/unparseable replica: its evidence simply doesn't count.
    }
  }
  return v;
}

// Crash-looping / never-ready engine pods are FENCED: they count against
// the Deployment's desired replicas (they hold a slot) but are excluded
// from victim selection and freeze scale-up — otherwise one bad image
// turns "ready < hint" into maxReplicas copies of the same crash loop.
std::vector<std::string> fenced_engine_pods(const K8sClient& k8s,
                                            const std::string& base_model) {
  std::vector<std::string> fenced;
  Json list = k8s.list(kCoreV1, "pods", "model%3D" + base_model);
  for (const auto& pod : list.at("items").items()) {
    bool bad = false;
    for (const auto& cs : pod.at({"status", "containerStatuses"}).items()) {
      const std::string reason =
          cs.at({"state", "waiting", "reason"}).as_string();
      if (reason == "CrashLoopBackOff" || reason == "ImagePullBackOff" ||
          reason == "ErrImagePull" || cs.at("restartCount").as_int(0) >= 3)
        bad = true;
    }
    if (bad) fenced.push_back(pod.at({"metadata", "name"}).as_string());
  }
  std::sort(fenced.begin(), fenced.end());
  return fenced;
}

// Victim = the engine the router fleet scores lowest (least routed
// in-flight per /debug/fleet). Falls back to the last pod by name when no
// router can answer — deterministic either way.
const EnginePod* pick_victim(const std::vector<RouterReplica>& routers,
                             const std::vector<EnginePod>& ready) {
  if (ready.empty()) return nullptr;
  for (const auto& r : routers) {
    try {
      auto resp = http_request("GET", r.base + "/debug/fleet", "", "", 5);
      if (!resp.ok()) continue;
      Json fleet = Json::parse(resp.body);
      const Json& engines = fleet.at("engines");
      long best = LONG_MAX;
      const EnginePod* victim = nullptr;
      for (const auto& pod : ready) {
        const long in_flight =
            engines.at(pod.base).at("in_flight_total").as_int(0);
        // <= so name-order ties break toward the LAST pod: matches the
        // no-router fallback, so flapping router reachability cannot flap
        // the victim choice between passes.
        if (in_flight <= best) {
          best = in_flight;
          victim = &pod;
        }
      }
      if (victim != nullptr) return victim;
    } catch (...) {
    }
  }
  return &ready.back();
}

void set_deployment_replicas(const K8sClient& k8s, const std::string& name,
                             long replicas) {
  auto dep = k8s.get(kAppsV1, "deployments", name);
  if (!dep) return;
  Json updated = *dep;
  updated["spec"]["replicas"] = replicas;
  k8s.replace(kAppsV1, "deployments", name, updated);
}

// POST an engine-admin action (drain/sleep/wake_up) THROUGH a router so
// service discovery marks the endpoint unroutable/routable in the same
// breath (request_service.py route_drain_request / route_sleep_wakeup) —
// falling back to the engine directly when no router is reachable (the
// probes reconcile discovery on the next cycle).
bool engine_admin_post(const std::vector<RouterReplica>& routers,
                       const std::string& engine_base,
                       const std::string& action, const std::string& params,
                       int timeout_s) {
  for (const auto& r : routers) {
    try {
      auto resp = http_request(
          "POST", r.base + "/" + action + "?url=" + engine_base + params, "",
          "", timeout_s);
      if (resp.ok()) return true;
    } catch (...) {
    }
  }
  try {
    return http_request("POST", engine_base + "/" + action +
                        (params.empty() ? "" : "?" + params.substr(1)),
                        "", "", timeout_s)
        .ok();
  } catch (...) {
    return false;
  }
}

// The full actuator for one autoscale-enabled TPURuntime. Returns status
// fields (desiredReplicas, idleStreak, lastScaleEpoch, fencedPods,
// sleeping, lastAutoscaleAction, replicaHint, routersPolled) — hysteresis
// state RIDES THE CR STATUS so `--once` passes (tests/CI) and operator
// restarts resume mid-cooldown instead of forgetting it.
Json autoscale_tpu_runtime(const K8sClient& k8s, const Json& cr) {
  const Json& as = cr.at({"spec", "autoscale"});
  const std::string cr_name = cr.at({"metadata", "name"}).as_string();
  const std::string dep_name = cr_name + "-engine";

  const long min_r = std::max(as.at("minReplicas").as_int(1), 0L);
  const long max_r = std::max(as.at("maxReplicas").as_int(8), min_r);
  const long stabilization_s = as.at("scaleDownStabilizationS").as_int(300);
  const long drain_deadline_s = as.at("drainDeadlineS").as_int(120);
  const long idle_verdicts = std::max(as.at("idleVerdicts").as_int(3), 1L);
  const bool scale_to_zero = as.at("scaleToZero").as_bool(false);
  // Scale-to-zero keeps ONE engine — slept, compile cache warm on disk —
  // so the floor never reaches an empty Deployment even when minReplicas=0.
  const long floor_r = std::max(min_r, 1L);

  const Json& st = cr.at("status");
  long idle_streak = st.at("idleStreak").as_int(0);
  long last_scale = st.at("lastScaleEpoch").as_int(0);
  bool sleeping = st.at("sleeping").as_bool(false);

  long current = floor_r;
  if (auto dep = k8s.get(kAppsV1, "deployments", dep_name))
    current = std::max(dep->at({"spec", "replicas"}).as_int(floor_r), 1L);

  const auto routers = router_replicas(k8s);
  const SignalView sig = poll_signal(routers);
  const auto fenced = fenced_engine_pods(k8s, cr_name);

  Json status = Json::object();
  Json fenced_json = Json::array();
  for (const auto& name : fenced) fenced_json.push_back(Json(name));
  status["fencedPods"] = fenced_json;
  status["routersPolled"] = sig.routers;
  status["replicaHint"] = sig.hint;

  if (sig.routers == 0) {
    // Zero evidence — hold position. An unreachable router fleet must
    // never read as "idle fleet": actuating blind is how autoscalers
    // delete the replicas that were busy serving.
    status["desiredReplicas"] = current;
    status["idleStreak"] = idle_streak;
    status["lastScaleEpoch"] = last_scale;
    status["sleeping"] = sleeping;
    status["lastAutoscaleAction"] = "hold_no_signal";
    return status;
  }

  long desired = std::min(std::max(sig.hint, floor_r), max_r);
  const long now = time(nullptr);
  std::string action = "none";

  // Idle verdict: nothing queued and the hint does not ask for more than we
  // run. Genuine surplus (hint < current) counts even with streams still in
  // flight — the blocking drain is what protects them; an exact-fit hint
  // (hint == current) counts only when the fleet is fully quiet, so the
  // streak can arm scale-to-zero at the floor but a momentary load dip
  // never pre-arms a scale-down. N consecutive verdicts arm the shrink
  // paths; any pressure resets the streak (anti-flap hysteresis).
  const bool idle =
      sig.queue_depth == 0 && sig.hint <= current &&
      (sig.hint < current || sig.in_flight == 0);
  idle_streak = idle ? idle_streak + 1 : 0;

  if (desired > current) {
    if (!fenced.empty()) {
      // Failure-aware: fenced pods already hold replica slots; piling more
      // replicas onto a crash loop is fuel, not capacity.
      action = "hold_fenced";
      desired = current;
    } else {
      set_deployment_replicas(k8s, dep_name, desired);
      if (sleeping) {
        // Surge while parked at zero: wake the slept standby FIRST — it
        // serves from its warm compile cache while the new pods come up.
        auto ready = ready_engine_pods(k8s, cr_name);
        if (!ready.empty())
          engine_admin_post(routers, ready.front().base, "wake_up", "", 10);
        sleeping = false;
      }
      last_scale = now;
      idle_streak = 0;
      current = desired;
      action = "scale_up";
    }
  } else if (desired < current) {
    if (idle_streak < idle_verdicts) {
      action = "hold_streak";
    } else if (now - last_scale < stabilization_s) {
      action = "hold_cooldown";
    } else if (!fenced.empty()) {
      // A fenced pod is the obvious victim: it serves nothing, so no
      // drain — shrink the Deployment and delete the broken pod.
      set_deployment_replicas(k8s, dep_name, current - 1);
      k8s.destroy(kCoreV1, "pods", fenced.front());
      last_scale = now;
      idle_streak = 0;
      action = "scale_down_fenced";
      current -= 1;
    } else {
      auto ready = ready_engine_pods(k8s, cr_name);
      const EnginePod* victim = pick_victim(routers, ready);
      if (victim == nullptr) {
        action = "hold_no_victim";
      } else {
        // Graceful ordering: drain THROUGH the router (discovery marks
        // the endpoint unroutable before the engine sees the POST), block
        // until in-flight work finishes or the drain deadline passes,
        // and only then shrink the Deployment and delete the pod —
        // SIGKILL never lands on a streaming response.
        engine_admin_post(
            routers, victim->base, "drain",
            "&wait=1&timeout=" + std::to_string(drain_deadline_s),
            static_cast<int>(drain_deadline_s) + 10);
        set_deployment_replicas(k8s, dep_name, current - 1);
        // Deleting the drained pod explicitly (instead of letting the
        // ReplicaSet pick) is what makes the drain meaningful; on a real
        // API server the pod-deletion-cost annotation would remove the
        // remaining race with the ReplicaSet controller.
        k8s.destroy(kCoreV1, "pods", victim->name);
        last_scale = now;
        idle_streak = 0;
        action = "scale_down";
        current -= 1;
      }
    }
  }

  // Pre-warmed scale-to-zero (docs/autoscaling.md "Scale to zero"): parked
  // at the floor with a fully idle fleet, the last engine sleeps — KV
  // freed, compile cache warm on disk. The FIRST admission-queue arrival
  // wakes it through the router (request_service wake-on-arrival); the
  // operator also wakes on queue evidence as the slower backstop.
  if (scale_to_zero && current == floor_r && action == "none") {
    // Sleeping is stricter than shrinking: no drain protects a slept
    // engine, so the fleet must be FULLY quiet, not merely surplus.
    if (!sleeping && idle && sig.in_flight == 0 &&
        idle_streak >= idle_verdicts) {
      auto ready = ready_engine_pods(k8s, cr_name);
      if (!ready.empty() &&
          engine_admin_post(routers, ready.front().base, "sleep", "&level=1",
                            10)) {
        sleeping = true;
        action = "sleep";
      }
    } else if (sleeping &&
               (sig.queue_depth > 0 || sig.in_flight > 0 ||
                sig.hint > current)) {
      auto ready = ready_engine_pods(k8s, cr_name);
      if (!ready.empty())
        engine_admin_post(routers, ready.front().base, "wake_up", "", 10);
      sleeping = false;
      action = "wake";
    }
  }

  status["desiredReplicas"] = desired;
  status["idleStreak"] = idle_streak;
  status["lastScaleEpoch"] = last_scale;
  status["sleeping"] = sleeping;
  status["lastAutoscaleAction"] = action;
  return status;
}

}  // namespace

ReconcileResult reconcile_tpu_runtime(const K8sClient& k8s, const Json& cr) {
  ReconcileResult result;
  const std::string ns = k8s.ns();
  bool changed = false;
  changed |= upsert(k8s, kCoreV1, "services", build_engine_service(cr, ns));
  if (cr.at({"spec", "storage", "enabled"}).as_bool(false)) {
    const std::string pvc_name =
        cr.at({"metadata", "name"}).as_string() + "-pvc";
    if (!k8s.get(kCoreV1, "persistentvolumeclaims", pvc_name))
      k8s.create(kCoreV1, "persistentvolumeclaims", build_engine_pvc(cr, ns));
  }
  changed |= upsert(k8s, kAppsV1, "deployments", build_engine_deployment(cr, ns));

  // Autoscale actuation runs AFTER the structural upserts so a fresh CR's
  // first pass creates the Deployment the actuator then scales.
  Json status = Json::object();
  if (cr.at("spec").has("autoscale")) {
    try {
      status = autoscale_tpu_runtime(k8s, cr);
      const std::string action =
          status.at("lastAutoscaleAction").as_string_or("none");
      if (action.rfind("scale", 0) == 0 || action == "sleep" ||
          action == "wake")
        changed = true;
    } catch (const std::exception& e) {
      fprintf(stderr, "[operator] tpuruntimes/%s: autoscale pass failed: %s\n",
              cr.at({"metadata", "name"}).as_string().c_str(), e.what());
    }
  }

  // Status: ready replicas from the owned Deployment.
  const std::string dep_name =
      cr.at({"metadata", "name"}).as_string() + "-engine";
  long ready = 0;
  if (auto dep = k8s.get(kAppsV1, "deployments", dep_name))
    ready = dep->at({"status", "readyReplicas"}).as_int(0);
  status["readyReplicas"] = ready;
  status["phase"] = status.at("sleeping").as_bool(false)
                        ? "Sleeping"
                        : (ready > 0 ? "Ready" : "Pending");
  status["lastReconciled"] = now_rfc3339();
  k8s.patch_status(kPstV1, "tpuruntimes",
                   cr.at({"metadata", "name"}).as_string(), status);
  result.changed = changed;
  result.phase = status.at("phase").as_string();
  return result;
}

// ---------------------------------------------------------------------------
// TPURouter
// ---------------------------------------------------------------------------

Json build_router_deployment(const Json& cr, const std::string& ns) {
  const Json& spec = cr.at("spec");
  const std::string name = cr.at({"metadata", "name"}).as_string() + "-router";

  Json args = Json::array();
  push_arg(args, "--host", "0.0.0.0");
  push_arg_num(args, "--port", spec.at("port").as_int(8000));
  push_arg(args, "--service-discovery",
           spec.at("serviceDiscovery").as_string_or("k8s"));
  if (spec.at("serviceDiscovery").as_string_or("k8s") == "k8s") {
    push_arg(args, "--k8s-namespace", ns);
    push_arg(args, "--k8s-label-selector",
             spec.at("k8sLabelSelector")
                 .as_string_or("environment=production-stack-tpu"));
  }
  push_arg(args, "--routing-logic",
           spec.at("routingLogic").as_string_or("roundrobin"));
  if (spec.has("sessionKey"))
    push_arg(args, "--session-key", spec.at("sessionKey").as_string());
  if (spec.has("cacheControllerUrl"))
    push_arg(args, "--cache-controller-url",
             spec.at("cacheControllerUrl").as_string());
  for (const auto& extra : spec.at("extraArgs").items()) args.push_back(extra);

  Json container = Json::object();
  container["name"] = "router";
  container["image"] = spec.at("image").as_string_or(
      "ghcr.io/production-stack-tpu/router:0.1.0");
  Json cmd = Json::array();
  cmd.push_back("pst-router");
  container["command"] = cmd;
  container["args"] = args;
  Json port = Json::object();
  port["containerPort"] = spec.at("port").as_int(8000);
  Json ports = Json::array();
  ports.push_back(port);
  container["ports"] = ports;

  Json containers = Json::array();
  containers.push_back(container);
  Json pod_spec = Json::object();
  pod_spec["containers"] = containers;
  if (spec.has("serviceAccountName"))
    pod_spec["serviceAccountName"] = spec.at("serviceAccountName").as_string();

  Json pod_labels = Json::object();
  pod_labels["app"] = name;
  Json pod_meta = Json::object();
  pod_meta["labels"] = pod_labels;
  Json tmpl = Json::object();
  tmpl["metadata"] = pod_meta;
  tmpl["spec"] = pod_spec;

  Json match = Json::object();
  match["app"] = name;
  Json selector = Json::object();
  selector["matchLabels"] = match;

  Json dspec = Json::object();
  dspec["replicas"] = spec.at("replicas").as_int(1);
  dspec["selector"] = selector;
  dspec["template"] = tmpl;

  Json dep = Json::object();
  dep["apiVersion"] = "apps/v1";
  dep["kind"] = "Deployment";
  dep["metadata"] = meta_for(cr, name, ns, "router");
  dep["spec"] = dspec;
  return dep;
}

Json build_router_service(const Json& cr, const std::string& ns) {
  const std::string name = cr.at({"metadata", "name"}).as_string() + "-router";
  Json svc = Json::object();
  svc["apiVersion"] = "v1";
  svc["kind"] = "Service";
  svc["metadata"] = meta_for(cr, name, ns, "router");
  Json sel = Json::object();
  sel["app"] = name;
  Json port = Json::object();
  port["port"] = cr.at({"spec", "servicePort"}).as_int(80);
  port["targetPort"] = cr.at({"spec", "port"}).as_int(8000);
  Json ports = Json::array();
  ports.push_back(port);
  Json sspec = Json::object();
  sspec["selector"] = sel;
  sspec["ports"] = ports;
  sspec["type"] = cr.at({"spec", "serviceType"}).as_string_or("ClusterIP");
  svc["spec"] = sspec;
  return svc;
}

ReconcileResult reconcile_tpu_router(const K8sClient& k8s, const Json& cr) {
  ReconcileResult result;
  const std::string ns = k8s.ns();
  bool changed = false;
  changed |= upsert(k8s, kCoreV1, "services", build_router_service(cr, ns));
  changed |= upsert(k8s, kAppsV1, "deployments", build_router_deployment(cr, ns));

  const std::string dep_name =
      cr.at({"metadata", "name"}).as_string() + "-router";
  long ready = 0;
  if (auto dep = k8s.get(kAppsV1, "deployments", dep_name))
    ready = dep->at({"status", "readyReplicas"}).as_int(0);
  // activeRuntimes: reference counts VLLMRuntimes (vllmrouter_controller.go:390).
  long runtimes = 0;
  try {
    runtimes = static_cast<long>(
        k8s.list(kPstV1, "tpuruntimes").at("items").items().size());
  } catch (...) {
  }
  Json status = Json::object();
  status["readyReplicas"] = ready;
  status["activeRuntimes"] = runtimes;
  status["phase"] = ready > 0 ? "Ready" : "Pending";
  status["lastReconciled"] = now_rfc3339();
  k8s.patch_status(kPstV1, "tpurouters",
                   cr.at({"metadata", "name"}).as_string(), status);
  result.changed = changed;
  result.phase = status.at("phase").as_string();
  return result;
}

// ---------------------------------------------------------------------------
// CacheServer
// ---------------------------------------------------------------------------

Json build_cache_server_deployment(const Json& cr, const std::string& ns) {
  const Json& spec = cr.at("spec");
  const std::string name =
      cr.at({"metadata", "name"}).as_string() + "-cache-server";
  Json args = Json::array();
  push_arg(args, "--host", "0.0.0.0");
  push_arg_num(args, "--port", spec.at("port").as_int(8100));
  push_arg_num(args, "--max-bytes",
               spec.at("maxBytes").as_int(8l << 30));
  Json container = Json::object();
  container["name"] = "cache-server";
  container["image"] = spec.at("image").as_string_or(
      "ghcr.io/production-stack-tpu/engine:0.1.0");
  Json cmd = Json::array();
  cmd.push_back("pst-kv-server");
  container["command"] = cmd;
  container["args"] = args;
  Json containers = Json::array();
  containers.push_back(container);
  Json pod_spec = Json::object();
  pod_spec["containers"] = containers;
  Json pod_labels = Json::object();
  pod_labels["app"] = name;
  Json pod_meta = Json::object();
  pod_meta["labels"] = pod_labels;
  Json tmpl = Json::object();
  tmpl["metadata"] = pod_meta;
  tmpl["spec"] = pod_spec;
  Json match = Json::object();
  match["app"] = name;
  Json selector = Json::object();
  selector["matchLabels"] = match;
  Json dspec = Json::object();
  dspec["replicas"] = spec.at("replicas").as_int(1);
  dspec["selector"] = selector;
  dspec["template"] = tmpl;
  Json dep = Json::object();
  dep["apiVersion"] = "apps/v1";
  dep["kind"] = "Deployment";
  dep["metadata"] = meta_for(cr, name, ns, "cache-server");
  dep["spec"] = dspec;
  return dep;
}

Json build_cache_server_service(const Json& cr, const std::string& ns) {
  const std::string name =
      cr.at({"metadata", "name"}).as_string() + "-cache-server";
  Json svc = Json::object();
  svc["apiVersion"] = "v1";
  svc["kind"] = "Service";
  svc["metadata"] = meta_for(cr, name, ns, "cache-server");
  Json sel = Json::object();
  sel["app"] = name;
  Json port = Json::object();
  port["port"] = cr.at({"spec", "port"}).as_int(8100);
  port["targetPort"] = cr.at({"spec", "port"}).as_int(8100);
  Json ports = Json::array();
  ports.push_back(port);
  Json sspec = Json::object();
  sspec["selector"] = sel;
  sspec["ports"] = ports;
  svc["spec"] = sspec;
  return svc;
}

ReconcileResult reconcile_cache_server(const K8sClient& k8s, const Json& cr) {
  ReconcileResult result;
  const std::string ns = k8s.ns();
  bool changed = false;
  changed |= upsert(k8s, kCoreV1, "services", build_cache_server_service(cr, ns));
  changed |=
      upsert(k8s, kAppsV1, "deployments", build_cache_server_deployment(cr, ns));
  Json status = Json::object();
  status["phase"] = "Ready";
  status["lastReconciled"] = now_rfc3339();
  k8s.patch_status(kPstV1, "cacheservers",
                   cr.at({"metadata", "name"}).as_string(), status);
  result.changed = changed;
  result.phase = "Ready";
  return result;
}

// ---------------------------------------------------------------------------
// LoraAdapter
// ---------------------------------------------------------------------------

namespace {

bool adapter_loaded(const std::string& base, const std::string& adapter) {
  try {
    auto resp = http_request("GET", base + "/v1/models", "", "", 5);
    if (!resp.ok()) return false;
    Json models = Json::parse(resp.body);
    for (const auto& m : models.at("data").items())
      if (m.at("id").as_string() == adapter) return true;
  } catch (...) {
  }
  return false;
}

bool post_adapter(const std::string& base, const std::string& endpoint,
                  const std::string& adapter, const std::string& path) {
  Json body = Json::object();
  body["lora_name"] = adapter;
  if (!path.empty()) body["lora_path"] = path;
  try {
    auto resp = http_request("POST", base + endpoint, body.dump(),
                             "application/json", 10);
    return resp.ok();
  } catch (...) {
    return false;
  }
}

}  // namespace

namespace {

const char* kLoraFinalizer = "pst.production-stack.io/lora-unload";

bool has_lora_finalizer(const Json& cr) {
  for (const auto& f : cr.at({"metadata", "finalizers"}).items())
    if (f.as_string() == kLoraFinalizer) return true;
  return false;
}

}  // namespace

ReconcileResult reconcile_lora_adapter(const K8sClient& k8s, const Json& cr) {
  // Placement algorithms follow the reference semantics
  // (loraadapter_controller.go:394 getOptimalPlacement):
  //   default   — load on every ready pod
  //   ordered   — first N pods by name
  //   equalized — N pods chosen round-robin by a stable hash offset, so
  //               multiple adapters spread across the fleet
  ReconcileResult result;
  const Json& spec = cr.at("spec");
  const std::string cr_name = cr.at({"metadata", "name"}).as_string();
  const std::string adapter = spec.at("adapterName").as_string_or(cr_name);
  const std::string path = spec.at("adapterPath").as_string_or("");
  const std::string base_model = spec.at("baseModel").as_string();

  // Finalizer-based deletion (reference handleDeletion,
  // loraadapter_controller.go:868): a deleted CR first unloads the adapter
  // from every pod that still serves it, then releases the finalizer so the
  // API server can drop the object. Without this a delete between passes
  // would strand adapters on pods forever.
  const bool deleting =
      !cr.at({"metadata", "deletionTimestamp"}).as_string_or("").empty();
  if (deleting) {
    // Unload is posted to EVERY matching pod unconditionally: probing
    // adapter_loaded() first would let a transiently-unreachable pod read
    // as "not loaded", release the finalizer, and strand the adapter on
    // that pod forever. Unloading an absent adapter is a no-op server-side;
    // an unreachable pod fails the POST and holds the finalizer for the
    // next reconcile.
    auto pods = ready_engine_pods(k8s, base_model);
    bool all_unloaded = true;
    for (const auto& pod : pods) {
      all_unloaded &=
          post_adapter(pod.base, "/v1/unload_lora_adapter", adapter, "");
    }
    if (all_unloaded && has_lora_finalizer(cr)) {
      Json updated = cr;
      Json remaining = Json::array();
      for (const auto& f : cr.at({"metadata", "finalizers"}).items())
        if (f.as_string() != kLoraFinalizer) remaining.push_back(f);
      updated["metadata"]["finalizers"] = remaining;
      k8s.replace(kPstV1, "loraadapters", cr_name, updated);
    }
    result.changed = true;
    result.phase = "Deleting";
    return result;
  }
  if (!has_lora_finalizer(cr)) {
    Json updated = cr;
    Json finalizers = Json::array();
    for (const auto& f : cr.at({"metadata", "finalizers"}).items())
      finalizers.push_back(f);
    finalizers.push_back(Json(std::string(kLoraFinalizer)));
    updated["metadata"]["finalizers"] = finalizers;
    try {
      k8s.replace(kPstV1, "loraadapters", cr_name, updated);
    } catch (const std::exception& e) {
      fprintf(stderr, "[operator] loraadapters/%s: finalizer add failed: %s\n",
              cr_name.c_str(), e.what());
    }
  }
  const std::string algo =
      spec.at({"placement", "algorithm"}).as_string_or("default");
  long want = spec.at({"placement", "replicas"}).as_int(0);

  auto pods = ready_engine_pods(k8s, base_model);
  std::vector<EnginePod> desired;
  if (algo == "default" || want <= 0 ||
      want >= static_cast<long>(pods.size())) {
    desired = pods;
  } else if (algo == "ordered") {
    desired.assign(pods.begin(), pods.begin() + want);
  } else {  // equalized
    size_t offset = 0;
    for (unsigned char c : adapter) offset = offset * 31 + c;
    for (long i = 0; i < want; ++i)
      desired.push_back(pods[(offset + static_cast<size_t>(i)) % pods.size()]);
  }

  Json loaded = Json::array();
  bool changed = false;
  for (const auto& pod : pods) {
    const bool should_have =
        std::any_of(desired.begin(), desired.end(),
                    [&](const EnginePod& p) { return p.name == pod.name; });
    const bool has = adapter_loaded(pod.base, adapter);
    if (should_have && !has) {
      changed |= post_adapter(pod.base, "/v1/load_lora_adapter", adapter, path);
    } else if (!should_have && has) {
      changed |= post_adapter(pod.base, "/v1/unload_lora_adapter", adapter, "");
    }
    if (should_have) loaded.push_back(pod.name);
  }

  Json status = Json::object();
  status["loadedPods"] = loaded;
  status["phase"] = loaded.items().empty() ? "Pending" : "Ready";
  status["lastReconciled"] = now_rfc3339();
  k8s.patch_status(kPstV1, "loraadapters",
                   cr.at({"metadata", "name"}).as_string(), status);
  result.changed = changed;
  result.phase = status.at("phase").as_string();
  return result;
}

}  // namespace pst
