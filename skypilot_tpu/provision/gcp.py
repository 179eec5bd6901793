"""GCP TPU provisioner: real slices via the Cloud TPU REST API.

Implements the provision SPI (skypilot_tpu/provision/__init__.py) against
``tpu.googleapis.com``. Reference analog:
sky/provision/gcp/instance_utils.py:1185-1620 (GCPTPUVMInstance — node API
create/stop/delete, state machine READY/CREATING/..., label filtering) and
the failover error classification in sky/backends/cloud_vm_ray_backend.py:997-1051
(quota → region blocklist, stockout/code 8 → zone blocklist, preempted
during creation/code 3, insufficient reservation/code 9).

TPU-native differences from the reference:

* **Multi-host slices go through the v2 ``queuedResources`` API**, which is
  the only way GCP guarantees slice-atomic allocation of v5e/v5p/v6e pods —
  all hosts come up together or the request fails as a unit (the hardware
  analog of the reference's STRICT_SPREAD placement group). Single-host
  slices use the plain node API, like the reference.
* An "instance" in the SPI is a *slice host* (TPU VM worker). One node
  resource fans out to ``hosts_per_slice`` InstanceInfos via its
  ``networkEndpoints`` — rank order is the endpoint order, which libtpu
  also uses for the ICI topology.

All HTTP goes through :func:`rest` so hermetic tests can monkeypatch a fake
TPU service; nothing below this module imports a cloud SDK (the reference's
lazy-adaptor discipline, sky/adaptors/common.py:7).
"""
from __future__ import annotations

import functools
import hashlib
import json
import subprocess
import time
from typing import Any, Dict, List, Optional, Tuple

from skypilot_tpu import exceptions
from skypilot_tpu.provision.common import (ClusterInfo, InstanceInfo,
                                           ProvisionRecord)

PROVIDER_NAME = "gcp"
TPU_API_BASE = "https://tpu.googleapis.com/v2"
COMPUTE_API_BASE = "https://compute.googleapis.com/compute/v1"

# Node lifecycle states (Cloud TPU v2 API) → SPI status strings consumed by
# core._refresh_one / jobs.controller / serve.replica_managers.
_PENDING_STATES = ("CREATING", "STARTING", "RESTARTING", "REPAIRING")
_STATE_MAP = {
    "READY": "running",
    "CREATING": "pending",
    "STARTING": "pending",
    "RESTARTING": "pending",
    "REPAIRING": "pending",
    "STOPPING": "stopping",
    "STOPPED": "stopped",
    "SUSPENDING": "stopping",
    "SUSPENDED": "stopped",
    "PREEMPTED": "preempted",
    "TERMINATED": "terminated",
    "HIDING": "terminated",
    "HIDDEN": "terminated",
    "DELETING": "terminated",
}

_CLUSTER_LABEL = "stpu-cluster"
_SLICE_LABEL = "stpu-slice"

_POLL_INTERVAL_SECONDS = 5
_CREATE_TIMEOUT_SECONDS = 900


class GcpApiError(exceptions.SkyTpuError):
    """An HTTP error from the TPU API, with the parsed error body."""

    def __init__(self, status: int, body: Dict[str, Any], context: str = ""):
        self.status = status
        self.body = body or {}
        err = self.body.get("error", {})
        self.code = err.get("status") or err.get("code")
        self.message = err.get("message", "")
        super().__init__(
            f"TPU API error {status} ({self.code}) {context}: "
            f"{self.message}")


# ---------------------------------------------------------------- transport
@functools.lru_cache(maxsize=1)
def _gcloud_project() -> str:
    proc = subprocess.run(
        ["gcloud", "config", "get-value", "project"],
        capture_output=True, text=True, timeout=30, check=False)
    project = proc.stdout.strip()
    if proc.returncode != 0 or not project or project == "(unset)":
        raise exceptions.NoCloudAccessError(
            "No GCP project configured (gcloud config set project ...).")
    return project


_token_cache: List[Tuple[float, str]] = []


def _access_token() -> str:
    now = time.time()
    if _token_cache and _token_cache[0][0] > now:
        return _token_cache[0][1]
    proc = subprocess.run(
        ["gcloud", "auth", "print-access-token"],
        capture_output=True, text=True, timeout=30, check=False)
    token = proc.stdout.strip()
    if proc.returncode != 0 or not token:
        raise exceptions.NoCloudAccessError(
            "Could not obtain a GCP access token "
            "(run `gcloud auth login`).")
    _token_cache[:] = [(now + 240, token)]  # tokens live ~1h; refresh early
    return token


def rest(method: str, path: str, body: Optional[dict] = None,
         params: Optional[dict] = None) -> Dict[str, Any]:
    """One TPU-API call. ``path`` is relative to the API base
    (``projects/...``). Tests monkeypatch this symbol with a fake service;
    everything above it is then hermetically testable."""
    import requests  # lazy: only a real-cloud path needs it
    url = f"{TPU_API_BASE}/{path}"
    resp = requests.request(
        method, url, params=params or {}, json=body,
        headers={"Authorization": f"Bearer {_access_token()}"},
        timeout=60)
    try:
        payload = resp.json() if resp.content else {}
    except ValueError:
        payload = {"error": {"message": resp.text[:500]}}
    if resp.status_code >= 400:
        raise GcpApiError(resp.status_code, payload, f"{method} {path}")
    return payload


def compute_rest(method: str, path: str, body: Optional[dict] = None,
                 params: Optional[dict] = None) -> Dict[str, Any]:
    """One Compute-API call (firewall rules are a compute resource even
    for TPU VMs — reference: sky/provision/gcp/instance.py:594 routes
    TPU firewall ops through GCPComputeInstance). Same monkeypatchable
    shape as :func:`rest`; ``path`` is relative to the API base."""
    import requests  # lazy: only a real-cloud path needs it
    url = f"{COMPUTE_API_BASE}/{path}"
    resp = requests.request(
        method, url, params=params or {}, json=body,
        headers={"Authorization": f"Bearer {_access_token()}"},
        timeout=60)
    try:
        payload = resp.json() if resp.content else {}
    except ValueError:
        payload = {"error": {"message": resp.text[:500]}}
    if resp.status_code >= 400:
        raise GcpApiError(resp.status_code, payload, f"{method} {path}")
    return payload


def _project_of(config: dict) -> str:
    return config.get("project_id") or _gcloud_project()


def _parent(project: str, zone: str) -> str:
    return f"projects/{project}/locations/{zone}"


# ------------------------------------------------------------ error parsing
def _classify_provision_error(e: GcpApiError, zone: str,
                              region: Optional[str]) -> Exception:
    """Map a TPU-API failure onto failover scope, mirroring the reference's
    per-error blocklist parsing (cloud_vm_ray_backend.py:997-1051):
    stockout → skip zone; quota exhausted → skip region (or zone when the
    message says so); auth → not retryable anywhere."""
    msg = e.message or str(e)
    low = msg.lower()
    if e.status in (401, 403) or e.code in ("PERMISSION_DENIED",
                                            "UNAUTHENTICATED"):
        return exceptions.NoCloudAccessError(
            f"GCP TPU API access denied: {msg}")
    # gRPC code 8 (RESOURCE_EXHAUSTED) / "no more capacity": stockout.
    if e.code in ("RESOURCE_EXHAUSTED", 8) or "no more capacity" in low \
            or "out of capacity" in low or "stockout" in low:
        if "quota" in low and ("in region" in low or "per region" in low):
            return exceptions.ProvisionError(
                f"TPU quota exhausted in region: {msg}",
                blocklist_region=region or zone.rsplit("-", 1)[0])
        return exceptions.ProvisionError(
            f"TPU capacity unavailable in {zone}: {msg}",
            blocklist_zone=zone)
    # gRPC code 3: preempted during creation; code 9: insufficient
    # reserved capacity — both zone-scoped in the reference.
    if e.code in (3, 9, "FAILED_PRECONDITION") or \
            "while in state preempted" in low or \
            "insufficient reserved capacity" in low:
        return exceptions.ProvisionError(
            f"TPU creation failed in {zone}: {msg}", blocklist_zone=zone)
    if "quota" in low:
        return exceptions.ProvisionError(
            f"TPU quota exceeded: {msg}",
            blocklist_region=region or zone.rsplit("-", 1)[0])
    if e.status == 409 or e.code == "ALREADY_EXISTS":
        # Not a failure: creation raced a previous attempt.
        return exceptions.ProvisionError(
            f"TPU resource already exists: {msg}", retryable_in_zone=True)
    if e.status in (429, 500, 502, 503, 504) or e.code in (
            "UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED", "INTERNAL"):
        return exceptions.ProvisionError(
            f"Transient TPU API failure: {msg}", retryable_in_zone=True)
    return exceptions.ProvisionError(
        f"TPU provisioning failed in {zone}: {msg}", blocklist_zone=zone)


# ------------------------------------------------------------------- naming
def _node_id(cluster_name: str, slice_index: int) -> str:
    return f"{cluster_name}-s{slice_index}"


def _node_body(cluster_name: str, slice_index: int, config: dict) -> dict:
    labels = dict(config.get("labels") or {})
    labels[_CLUSTER_LABEL] = cluster_name
    labels[_SLICE_LABEL] = str(slice_index)
    body: Dict[str, Any] = {
        "acceleratorType": _gcp_accelerator_type(config["accelerator"]),
        "runtimeVersion": config.get("runtime_version")
                          or "tpu-ubuntu2204-base",
        "labels": labels,
        "metadata": config.get("metadata") or {},
        "dataDisks": [],
        "networkConfig": {"enableExternalIps": True},
        # Network tags: the cluster tag lets open_ports target this
        # cluster's rule without per-instance mutation (the reference
        # tags instances lazily at open_ports time,
        # sky/provision/gcp/instance.py:600-608; tagging at creation
        # makes open/cleanup order-independent), and the shared "stpu"
        # tag scopes the bootstrap ssh/internal rules to our hosts
        # only on shared VPCs.
        "tags": [_network_tag(cluster_name), _COMMON_TAG],
    }
    if config.get("use_spot"):
        body["schedulingConfig"] = {"preemptible": True}
    return body


def _gcp_accelerator_type(accelerator: str) -> str:
    """``tpu-v5e-16`` → GCP acceleratorType ``v5litepod-16`` etc.

    Our catalog names slices by generation + chip count; GCP's API uses
    core counts for v2-v4 (a chip is 2 cores there) and chip counts with
    marketing names for v5e/v5p/v6e (sky/clouds/service_catalog/
    gcp_catalog.py:215-237 performs the same translation)."""
    name = accelerator[len("tpu-"):] if accelerator.startswith("tpu-") \
        else accelerator
    gen, _, count_s = name.partition("-")
    count = int(count_s)
    if gen in ("v2", "v3", "v4"):
        return f"{gen}-{count * 2}"          # chips → cores
    mapping = {"v5e": "v5litepod", "v5p": "v5p", "v6e": "v6e"}
    return f"{mapping[gen]}-{count}"


# ---------------------------------------------------------------------- SPI
def run_instances(region: Optional[str], zone: Optional[str],
                  cluster_name: str, config: dict) -> ProvisionRecord:
    """Create (or resume) every slice of the cluster.

    Multi-host slices are created as queued resources (slice-atomic);
    single-host as plain nodes. Existing STOPPED nodes are restarted,
    READY/CREATING ones left alone — rerunning is idempotent, like the
    reference's resume path."""
    if zone is None:
        raise exceptions.ProvisionError(
            "gcp: a concrete zone is required to create TPU slices")
    project = _project_of(config)
    num_slices = int(config.get("num_slices", 1))
    hosts_per_slice = int(config.get("hosts_per_slice", 1))
    existing = _list_cluster_nodes(project, zone, cluster_name)

    created, resumed = [], []
    try:
        for s in range(num_slices):
            node_id = _node_id(cluster_name, s)
            node = existing.get(node_id)
            if node is not None:
                state = node.get("state")
                if state == "STOPPED":
                    rest("POST", f"{_parent(project, zone)}/nodes/"
                                 f"{node_id}:start")
                    resumed.append(node_id)
                elif state in _PENDING_STATES + ("READY",):
                    resumed.append(node_id)
                else:
                    # PREEMPTED/TERMINATED husk: delete then recreate.
                    _delete_node(project, zone, node_id)
                    _create_slice(project, zone, cluster_name, s,
                                  hosts_per_slice, config)
                    created.append(node_id)
            else:
                _create_slice(project, zone, cluster_name, s,
                              hosts_per_slice, config)
                created.append(node_id)
    except GcpApiError as e:
        raise _classify_provision_error(e, zone, region) from e
    return ProvisionRecord(
        provider_name=PROVIDER_NAME, region=region, zone=zone,
        cluster_name=cluster_name,
        head_instance_id=f"{_node_id(cluster_name, 0)}-w0",
        created_instance_ids=created,
        resumed_instance_ids=resumed)


def _create_slice(project: str, zone: str, cluster_name: str,
                  slice_index: int, hosts_per_slice: int,
                  config: dict) -> None:
    node_id = _node_id(cluster_name, slice_index)
    body = _node_body(cluster_name, slice_index, config)
    if hosts_per_slice > 1:
        # Slice-atomic allocation through queuedResources: every host of
        # the pod is granted together, or the request fails as one unit.
        qr_body: Dict[str, Any] = {
            "tpu": {"nodeSpec": [{
                "parent": _parent(project, zone),
                "nodeId": node_id,
                "node": body,
            }]},
        }
        if config.get("use_spot"):
            body.pop("schedulingConfig", None)
            qr_body["spot"] = {}
        rest("POST", f"{_parent(project, zone)}/queuedResources",
             body=qr_body, params={"queuedResourceId": node_id})
    else:
        rest("POST", f"{_parent(project, zone)}/nodes", body=body,
             params={"nodeId": node_id})


def _list_cluster_nodes(project: str, zone: str, cluster_name: str,
                        lenient_auth: bool = True) -> Dict[str, dict]:
    """All TPU nodes of this cluster in the zone, keyed by short node id.

    Server-side filtering is not supported for labels on the nodes.list
    API, so filter client-side like the reference
    (instance_utils.py:1285-1303). ``lenient_auth`` maps 403/404 to "no
    nodes" (status queries must not crash on unauthorized regions,
    reference :1270-1276); destructive paths pass False so a credential
    failure cannot masquerade as a successful teardown."""
    try:
        resp = rest("GET", f"{_parent(project, zone)}/nodes")
    except GcpApiError as e:
        if e.status == 404 or (lenient_auth and e.status == 403):
            return {}
        if e.status == 403:
            raise exceptions.NoCloudAccessError(
                f"TPU API access denied listing nodes in {zone}: "
                f"{e.message}") from e
        raise
    out = {}
    for node in resp.get("nodes", []):
        if node.get("labels", {}).get(_CLUSTER_LABEL) != cluster_name:
            continue
        short = node["name"].rsplit("/", 1)[-1]
        out[short] = node
    return out


def _delete_node(project: str, zone: str, node_id: str) -> None:
    try:
        rest("DELETE", f"{_parent(project, zone)}/nodes/{node_id}")
    except GcpApiError as e:
        if e.status != 404:
            raise
    # Queued resources leave a record that blocks re-creating the same id.
    try:
        rest("DELETE",
             f"{_parent(project, zone)}/queuedResources/{node_id}",
             params={"force": "true"})
    except GcpApiError as e:
        if e.status != 404:
            raise


def wait_instances(region: Optional[str], cluster_name: str,
                   state: str, provider_config: dict) -> None:
    """Poll until every slice reaches ``state`` ("running" == READY).

    A queued resource that lands in FAILED is surfaced as a ProvisionError
    with failover scope so the backend's retry loop can move on."""
    zone, project = _zone_project(provider_config, cluster_name)
    want = {"running": "READY", "stopped": "STOPPED"}[state]
    deadline = time.time() + _CREATE_TIMEOUT_SECONDS
    while time.time() < deadline:
        nodes = _list_cluster_nodes(project, zone, cluster_name)
        states = {n.get("state") for n in nodes.values()}
        if nodes and states == {want}:
            return
        bad = states - set(_PENDING_STATES) - {want, "STOPPING"}
        if bad:
            _raise_for_failed_creation(project, zone, cluster_name, bad,
                                       region)
        _check_queued_resources(project, zone, cluster_name, region)
        time.sleep(_POLL_INTERVAL_SECONDS)
    raise exceptions.ProvisionError(
        f"Timed out waiting for {cluster_name} to reach {state}",
        blocklist_zone=zone)


def _raise_for_failed_creation(project: str, zone: str, cluster_name: str,
                               bad_states: set, region) -> None:
    raise exceptions.ProvisionError(
        f"TPU slice(s) of {cluster_name} entered {sorted(bad_states)} "
        f"during provisioning in {zone}", blocklist_zone=zone)


def _check_queued_resources(project: str, zone: str, cluster_name: str,
                            region) -> None:
    try:
        resp = rest("GET", f"{_parent(project, zone)}/queuedResources")
    except GcpApiError:
        return
    for qr in resp.get("queuedResources", []):
        short = qr["name"].rsplit("/", 1)[-1]
        if not short.startswith(f"{cluster_name}-s"):
            continue
        qstate = qr.get("state", {}).get("state")
        if qstate in ("FAILED", "SUSPENDED", "SUSPENDING"):
            detail = json.dumps(
                qr.get("state", {}).get("stateInitiator", ""))
            raise exceptions.ProvisionError(
                f"Queued resource {short} became {qstate} in {zone}: "
                f"{detail}", blocklist_zone=zone)


def _zone_project(provider_config: dict,
                  cluster_name: str) -> Tuple[str, str]:
    """Zone/project come from provider_config, ALWAYS: the backend
    records them at provision time and get_cluster_info echoes them into
    every handle, so provision code never reaches back into the client
    state DB (which does not exist where a controller cluster runs —
    the r2 layering inversion this replaces)."""
    zone = provider_config.get("zone")
    if zone is None:
        raise exceptions.ProvisionError(
            f"gcp: provider_config for {cluster_name} carries no zone; "
            "the caller must pass the provisioning-time config "
            "(handle.cluster_info.provider_config).")
    return zone, _project_of(provider_config)


def query_instances(cluster_name: str,
                    provider_config: dict) -> Dict[str, str]:
    """Per-host status map. A slice host inherits its node's state — on a
    pod slice there is no per-worker lifecycle (the gang lives and dies
    together), which is exactly the slice-atomic semantics the backend's
    status reconciler expects."""
    zone, project = _zone_project(provider_config, cluster_name)
    out: Dict[str, str] = {}
    for node_id, node in _list_cluster_nodes(project, zone,
                                             cluster_name).items():
        status = _STATE_MAP.get(node.get("state", ""), "pending")
        hosts = max(1, len(node.get("networkEndpoints", []) or [1]))
        for w in range(hosts):
            out[f"{node_id}-w{w}"] = status
    return out


def get_cluster_info(region: Optional[str], cluster_name: str,
                     provider_config: dict) -> ClusterInfo:
    zone, project = _zone_project(provider_config, cluster_name)
    instances: Dict[str, InstanceInfo] = {}
    head_id: Optional[str] = None
    nodes = _list_cluster_nodes(project, zone, cluster_name)
    for node_id in sorted(nodes):
        node = nodes[node_id]
        slice_id = node_id.rsplit("-", 1)[-1]       # "s0", "s1", ...
        endpoints = node.get("networkEndpoints") or []
        if not endpoints:
            endpoints = [{}]
        for w, ep in enumerate(endpoints):
            iid = f"{node_id}-w{w}"
            access = ep.get("accessConfig") or {}
            instances[iid] = InstanceInfo(
                instance_id=iid,
                internal_ip=ep.get("ipAddress", ""),
                external_ip=access.get("externalIp"),
                slice_id=slice_id,
                host_index=w,
                tags={"node_id": node_id, "zone": zone})
            if head_id is None:
                head_id = iid
    return ClusterInfo(
        cluster_name=cluster_name, provider_name=PROVIDER_NAME,
        region=region or zone.rsplit("-", 1)[0], zone=zone,
        instances=instances, head_instance_id=head_id,
        ssh_user=provider_config.get("ssh_user", "stpu"),
        ssh_key_path=provider_config.get("ssh_key_path"),
        provider_config=dict(provider_config, zone=zone,
                             project_id=project))


def stop_instances(cluster_name: str, provider_config: dict) -> None:
    """Stop the cluster's nodes. Multi-host pods cannot stop — the TPU API
    rejects it — so refuse up front (the capability layer routes user
    `stop` requests away from pods before this; reference:
    sky/clouds/gcp.py:558-610 unstoppable-pod handling)."""
    zone, project = _zone_project(provider_config, cluster_name)
    # Destructive-path listing: a 403 must raise, not return {} — an empty
    # loop here would report "stopped" while the nodes keep billing.
    for node_id, node in _list_cluster_nodes(project, zone, cluster_name,
                                             lenient_auth=False).items():
        if len(node.get("networkEndpoints") or []) > 1:
            raise exceptions.NotSupportedError(
                f"TPU pod slice {node_id} cannot be stopped; only "
                "single-host slices support stop. Use `down` instead.")
        if node.get("state") in ("READY",) + _PENDING_STATES:
            rest("POST", f"{_parent(project, zone)}/nodes/{node_id}:stop")


def terminate_instances(cluster_name: str, provider_config: dict) -> None:
    try:
        zone, project = _zone_project(provider_config, cluster_name)
    except exceptions.ProvisionError:
        return  # nothing recorded → nothing to clean
    for node_id in _list_cluster_nodes(project, zone, cluster_name,
                                       lenient_auth=False):
        _delete_node(project, zone, node_id)


# ------------------------------------------------------------------ ports
# Firewall management (provision SPI open_ports/cleanup_ports). Reference:
# sky/provision/__init__.py:122,133 declare the ops;
# sky/provision/gcp/instance.py:571,626 implement them with one
# per-cluster ingress rule targeting a cluster network tag. Differences
# here: SDK-free Compute REST (the repo's `rest` discipline), and hosts
# are tagged at node CREATION (_node_body) instead of lazily, so the rule
# applies to later-added slices automatically. The VPC itself is assumed
# to exist (default network unless provider_config["network"] says
# otherwise) — the reference's VPC/subnet bootstrap
# (sky/provision/gcp/config.py:392-540) is out of scope for TPU VMs,
# which GCP only places in pre-existing networks.

_OP_WAIT_TIMEOUT_SECONDS = 120


def _network_tag(cluster_name: str) -> str:
    """RFC1035-safe network tag for the cluster (lowercase, [a-z0-9-],
    63 chars), suffixed with a short hash of the RAW name: the
    sanitize/truncate steps are lossy (``a.b`` and ``a-b`` sanitize
    identically; two long names sharing a 57-char prefix truncate
    identically), and colliding tags would alias two clusters onto ONE
    firewall rule — tearing down either cluster then deletes the
    other's ingress (ADVICE round 5). The hash restores injectivity."""
    digest = hashlib.sha1(cluster_name.encode()).hexdigest()[:6]
    tag = "".join(c if c.isalnum() or c == "-" else "-"
                  for c in cluster_name.lower())
    # Room for "-<digest>" plus the "-ports" suffix rule names append.
    stem = ("stpu-" + tag.strip("-"))[:63 - 7 - 6].rstrip("-")
    return f"{stem}-{digest}"


def _legacy_network_tag(cluster_name: str) -> str:
    """Tag format before the hash suffix — kept so cleanup can delete
    rules created by earlier versions instead of leaking them."""
    tag = "".join(c if c.isalnum() or c == "-" else "-"
                  for c in cluster_name.lower())
    return ("stpu-" + tag.strip("-"))[:63].rstrip("-")


def _firewall_rule_name(cluster_name: str) -> str:
    return (_network_tag(cluster_name) + "-ports")[:63]


def _normalize_ports(ports) -> List[str]:
    """Resources.ports entries ("80", 8080, "30000-30100") → the compute
    API's allowed.ports strings (shared grammar:
    provision.common.parse_port_ranges)."""
    from skypilot_tpu.provision.common import parse_port_ranges
    out = [f"{lo}-{hi}" if hi != lo else str(lo)
           for lo, hi in parse_port_ranges(ports)]
    return sorted(set(out))


def _wait_compute_op(project: str, op: Dict[str, Any]) -> None:
    """Block until a global compute operation is DONE; raise on error."""
    name = op.get("name")
    if not name:
        return
    deadline = time.time() + _OP_WAIT_TIMEOUT_SECONDS
    while True:
        if op.get("status") == "DONE":
            errors = (op.get("error") or {}).get("errors")
            if errors:
                raise exceptions.ProvisionError(
                    f"firewall operation {name} failed: {errors}")
            return
        if time.time() > deadline:
            raise exceptions.ProvisionError(
                f"firewall operation {name} timed out")
        time.sleep(_POLL_INTERVAL_SECONDS)
        op = compute_rest(
            "GET", f"projects/{project}/global/operations/{name}")


def open_ports(cluster_name: str, ports: List[str],
               provider_config: dict) -> None:
    """Ensure one ingress rule allowing ``ports`` (tcp) to this
    cluster's tagged hosts. Idempotent: re-opening merges with whatever
    the rule already allows (a serve controller opens its LB range once;
    a later `launch` against the same cluster with task ports must not
    clobber it)."""
    if not ports:
        return
    project = _project_of(provider_config)
    network = provider_config.get("network") or "default"
    name = _firewall_rule_name(cluster_name)
    want = _normalize_ports(ports)
    try:
        existing = compute_rest(
            "GET", f"projects/{project}/global/firewalls/{name}")
    except GcpApiError as e:
        if e.status != 404:
            raise
        existing = None
    if existing is not None:
        have = []
        for allowed in existing.get("allowed", []):
            if allowed.get("IPProtocol") == "tcp":
                have.extend(allowed.get("ports", []))
        merged = sorted(set(have) | set(want))
        if merged == sorted(set(have)):
            return  # already open
        op = compute_rest(
            "PATCH", f"projects/{project}/global/firewalls/{name}",
            body={"allowed": [{"IPProtocol": "tcp", "ports": merged}]})
    else:
        op = compute_rest(
            "POST", f"projects/{project}/global/firewalls",
            body={
                "name": name,
                "network": f"projects/{project}/global/networks/"
                           f"{network}",
                "direction": "INGRESS",
                "sourceRanges": ["0.0.0.0/0"],
                "allowed": [{"IPProtocol": "tcp", "ports": want}],
                "targetTags": [_network_tag(cluster_name)],
                "description": f"stpu-managed ingress for cluster "
                               f"{cluster_name}",
            })
    _wait_compute_op(project, op)


def cleanup_ports(cluster_name: str, ports: List[str],
                  provider_config: dict) -> None:
    """Delete the cluster's ingress rule (the whole rule — ports is
    advisory, matching the reference's cleanup_ports contract which
    ignores it, sky/provision/gcp/instance.py:626). Both the current
    (hash-suffixed) and the legacy rule name are tried: a cluster
    provisioned before the tag format changed still tears its rule
    down instead of leaking ingress."""
    del ports
    project = _project_of(provider_config)
    legacy = _legacy_network_tag(cluster_name) + "-ports"
    for name in dict.fromkeys([_firewall_rule_name(cluster_name),
                               legacy[:63]]):
        try:
            op = compute_rest(
                "DELETE", f"projects/{project}/global/firewalls/{name}")
        except GcpApiError as e:
            if e.status == 404:
                continue  # never created / already gone
            raise
        _wait_compute_op(project, op)


# -------------------------------------------------------------- bootstrap
# Reference analog: bootstrap_instances in the provision SPI
# (sky/provision/__init__.py) backed by sky/provision/gcp/config.py:392-540
# + constants.py:57-194 — ensure the VPC is usable BEFORE any instance
# waits on it. Trimmed to what TPU VMs actually need: the network must
# exist (TPU VMs only join pre-existing networks — no VPC creation), SSH
# ingress must be open (or provisioner.wait_for_ssh hangs its full
# timeout on a locked-down project), and intra-VPC traffic must flow
# (gang drivers reach workers over internal IPs).

# Shared network tag carried by every stpu-provisioned host: bootstrap
# rules target it so a shared/pre-existing VPC's unrelated VMs are
# never exposed by our ingress (open_ports applies the same
# tag-scoping discipline per cluster).
_COMMON_TAG = "stpu"

_BOOTSTRAP_RULES = (
    # (suffix, body) — idempotent per network, targeted at stpu nodes.
    ("allow-ssh", {
        "direction": "INGRESS",
        "sourceRanges": ["0.0.0.0/0"],
        "targetTags": [_COMMON_TAG],
        "allowed": [{"IPProtocol": "tcp", "ports": ["22"]}],
        "description": "stpu bootstrap: ssh ingress for provisioning "
                       "(stpu-tagged hosts only)",
    }),
    ("allow-internal", {
        "direction": "INGRESS",
        # GCP auto-mode subnets live in 10.128.0.0/9 (the reference's
        # range, constants.py:71); custom-mode users with other ranges
        # manage internal rules themselves.
        "sourceRanges": ["10.128.0.0/9"],
        "targetTags": [_COMMON_TAG],
        "allowed": [{"IPProtocol": "tcp", "ports": ["0-65535"]},
                    {"IPProtocol": "udp", "ports": ["0-65535"]},
                    {"IPProtocol": "icmp"}],
        "description": "stpu bootstrap: intra-VPC traffic (gang "
                       "drivers, host agents, jax coordinator; "
                       "stpu-tagged hosts only)",
    }),
)


def bootstrap_instances(region, cluster_name: str,
                        provider_config: dict) -> None:
    """Pre-provision VPC sanity: verify the network exists and ensure
    the ssh/internal ingress rules a cluster needs are present.
    Idempotent; rules are per-network (shared by every cluster on it),
    not per-cluster — cleanup_ports never touches them, matching the
    reference's persistent bootstrap rules."""
    del cluster_name
    project = _project_of(provider_config)
    network = provider_config.get("network") or "default"
    try:
        compute_rest(
            "GET", f"projects/{project}/global/networks/{network}")
    except GcpApiError as e:
        if e.status == 404:
            # Project-global, permanent: failing over to another zone
            # cannot fix a missing VPC, so this must NOT be a
            # (retryable) ProvisionError.
            raise exceptions.NoCloudAccessError(
                f"VPC network {network!r} does not exist in project "
                f"{project!r}. TPU VMs only join pre-existing "
                "networks: create it (or set provider network config) "
                "first.") from e
        raise _classify_provision_error(e, zone=str(region),
                                        region=region) from e
    safe_net = "".join(c if c.isalnum() or c == "-" else "-"
                       for c in network.lower()).strip("-")[:40]
    for suffix, body in _BOOTSTRAP_RULES:
        name = f"stpu-{safe_net}-{suffix}"[:63]
        try:
            compute_rest(
                "GET", f"projects/{project}/global/firewalls/{name}")
            continue  # already bootstrapped
        except GcpApiError as e:
            if e.status != 404:
                raise _classify_provision_error(
                    e, zone=str(region), region=region) from e
        try:
            op = compute_rest(
                "POST", f"projects/{project}/global/firewalls",
                body={
                    "name": name,
                    "network": f"projects/{project}/global/networks/"
                               f"{network}",
                    **body,
                })
        except GcpApiError as e:
            if e.status == 409:
                continue  # concurrent launch won the create race
            raise _classify_provision_error(
                e, zone=str(region), region=region) from e
        _wait_compute_op(project, op)
