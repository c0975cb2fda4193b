"""Deterministic fault-injection plane (the Chaos-Monkey/Jepsen seam).

Every robustness claim in this tree -- retry, hedging, steal,
publish-last commit, burn-rate paging -- used to be exercised only by
hand-rolled monkeypatches scattered through tests. This module makes
faults a first-class, seeded, reproducible subsystem: one process-wide
`FaultPlane` holds declarative rules and every IO/device seam carries a
tap that consults it.

A rule is match + action + trigger:

  match    site glob (`backend.read`, `backend.*`, `rpc.*`, ...) plus
           optional tenant / key globs (key is the seam's natural
           operand: object key, RPC path, op name, peer addr).
  action   error (typed: backend_5xx, oserror, timeout, connection,
           transport, device_oom, compile_failure, does_not_exist),
           latency (added sleep), truncate (partial read), corrupt
           (deterministic byte flip), drop (black-hole; the seam
           decides what a drop means), wedge (block until released or
           the rule's window expires).
  trigger  probability `p`, every-`nth` matching call, an active
           window (`begin_s`/`for_s` relative to plane activation) and
           a `max_fires` cap.

Determinism: probability draws are NOT consumed from a shared PRNG
stream (thread interleaving would break replay) -- the decision for the
N-th matching call of rule R is a pure hash of (plane seed, rule index,
N). Two runs that issue the same per-rule call sequences inject exactly
the same faults; the bounded injection log is the replay artifact tests
compare byte for byte.

Activation: `TEMPO_CHAOS=<json | path | @path>` (checked lazily, once),
the app's `--chaos.rules`, or `configure()`/`POST /internal/chaos` at
runtime. With no plane configured every tap is a single `is None` check
-- zero overhead, zero behavior change (the faults-off differential in
tests/test_chaos.py holds the tree to that).

Surface: `tempo_chaos_injected_total{site,action}` rides the kerneltel
/metrics exposition; `/status/chaos` serves the active-rule list with
per-rule call/fire counts and the recent injection log.
"""

from __future__ import annotations

import fnmatch
import hashlib
import json
import threading
import time
from collections import deque
from dataclasses import asdict, dataclass, field

from ..util.metrics import Counter

ENV = "TEMPO_CHAOS"

LOG_MAX = 512  # injection-log entries kept for replay comparison

# every tapped seam, with the operand its `key` matches against
SITES = {
    "backend.read": "whole-object read (key: '<block>/<name>')",
    "backend.read_range": "ranged read; truncate/corrupt apply to the bytes",
    "backend.read_tenant": "tenant-object read (key: object name)",
    "backend.write": "object write / append open (key: '<block>/<name>'); "
                     "drop = the write is silently lost",
    "backend.write_tenant": "tenant-object write (key: object name); "
                            "drop = lost write",
    "backend.list": "tenants()/blocks() listings (key: tenant or '')",
    "backend.delete": "block / tenant-object / object deletes; "
                      "drop = the delete silently no-ops",
    "backend.copy": "backend-side part copies (key: '<src>/<name>'); "
                    "drop = the part is never copied",
    "rpc.client": "ingester-client HTTP calls (key: URL path)",
    "rpc.worker": "querier-worker poll/result posts (key: URL path)",
    "rpc.external": "querier calls to external serverless search "
                    "endpoints (key: endpoint URL); drop = endpoint "
                    "black-holed",
    "rpc.remotewrite": "metrics-generator remote-write pushes "
                       "(key: endpoint URL); drop = push silently lost",
    "device.launch": "device kernel launches (key: op name); "
                     "device_oom / compile_failure / slow launch",
    "wal.append": "WAL record append; truncate = torn tail, drop = lost",
    "wal.fsync": "WAL flush/fsync (error = failed stable write)",
    "gossip.sync": "outbound gossip push-pull (key: peer addr); "
                   "drop = partition this direction",
    "gossip.recv": "inbound gossip merge (drop = ignore peer state)",
}

ACTIONS = ("error", "latency", "truncate", "corrupt", "drop", "wedge")

# which sites can honor which data-shaped actions: truncate/corrupt
# need bytes flowing through the tap; drop needs a seam with "silently
# lost" semantics (a lost write/delete/copy/message). Rules whose site
# glob can reach NONE of the capable sites are rejected at parse time
# -- a drill that "injects" no-ops would certify robustness that was
# never exercised.
DATA_SITES = frozenset(
    {"backend.read", "backend.read_range", "backend.read_tenant",
     "wal.append"})
DROP_SITES = frozenset(
    {"backend.write", "backend.write_tenant", "backend.delete",
     "backend.copy", "wal.append", "gossip.sync", "gossip.recv",
     "rpc.client", "rpc.worker", "rpc.external", "rpc.remotewrite"})

# what a bare action="error" means per seam family: the error class the
# real world throws there (and the retry/breaker layers classify)
DEFAULT_ERROR = {
    "backend": "backend_5xx",
    "rpc.client": "transport",
    "rpc.worker": "oserror",
    "rpc.external": "transport",
    "rpc.remotewrite": "transport",
    "device": "device_oom",
    "wal": "oserror",
    "gossip": "connection",
}

# which module implements (taps) each seam, keyed by path relative to
# the package root. This is the contract the static checker's
# chaos-seam-gap rule enforces both ways: every SITES key must be
# claimed here, every claim must be real (the module names the site),
# and a module doing remote I/O in services/transport/fleet scope must
# appear here at all -- an empty tuple declares "this module is a fault
# *source*, not a seam" (the certification harness drives drills; its
# own urlopens are the measurement, not the system under test).
SEAM_MODULES = {
    "chaos/backendwrap.py": (
        "backend.read", "backend.read_range", "backend.read_tenant",
        "backend.write", "backend.write_tenant", "backend.list",
        "backend.delete", "backend.copy"),
    "transport/client.py": ("rpc.client",),
    "transport/gossip.py": ("gossip.sync", "gossip.recv"),
    "services/worker.py": ("rpc.worker",),
    "services/querier.py": ("rpc.external",),
    "services/remotewrite.py": ("rpc.remotewrite",),
    "ops/device.py": ("device.launch",),
    "db/wal.py": ("wal.append", "wal.fsync"),
    "fleet/harness.py": (),  # certification driver: fault source
    # a tree's supervisor: readiness probes and the status fan-out to its
    # own children; the jobs' wire is worker.py's seam
    "services/proctree.py": (),
}


class ChaosError(OSError):
    """Default injected fault: an OSError, i.e. retryable transport/IO."""


class ChaosDeviceOOM(RuntimeError):
    """XLA-shaped device OOM (deterministic: the query fails, the
    shard degrades; retrying the same launch would OOM again)."""


class ChaosCompileError(RuntimeError):
    """Simulated XLA compile failure."""


class _Drop:
    def __repr__(self):  # pragma: no cover - debugging aid
        return "<chaos DROP>"


DROP = _Drop()  # sentinel a tap returns when the seam should black-hole

INJECTED = Counter(
    "tempo_chaos_injected_total",
    help="chaos faults injected by site and action")


def _error_factory(name: str):
    if name == "backend_5xx":
        from ..backend.base import BackendError

        return BackendError("chaos: injected backend 5xx")
    if name == "does_not_exist":
        from ..backend.base import DoesNotExist

        return DoesNotExist("chaos: injected missing object")
    if name == "transport":
        from ..transport.client import TransportError

        return TransportError(503, "chaos: injected transport error")
    if name == "timeout":
        return TimeoutError("chaos: injected timeout")
    if name == "connection":
        return ConnectionError("chaos: injected connection reset")
    if name == "device_oom":
        return ChaosDeviceOOM("RESOURCE_EXHAUSTED: chaos: injected device OOM")
    if name == "compile_failure":
        return ChaosCompileError("chaos: injected XLA compile failure")
    return ChaosError(f"chaos: injected fault ({name or 'oserror'})")


def _default_error(site: str) -> str:
    for prefix, name in DEFAULT_ERROR.items():
        if site == prefix or site.startswith(prefix + "."):
            return name
    return "oserror"


@dataclass
class FaultRule:
    """One declarative rule; see module docstring for field meaning."""

    site: str
    action: str = "error"
    error: str = ""       # error class; "" = the site's natural default
    tenant: str = ""      # glob, "" = any
    key: str = ""         # glob, "" = any
    p: float = 1.0        # probability per matching call (unless nth set)
    nth: int = 0          # fire on every nth matching call (1-based)
    begin_s: float = 0.0  # window start, seconds since plane activation
    for_s: float = 0.0    # window length (0 = forever)
    max_fires: int = 0    # total fire cap (0 = unlimited)
    latency_s: float = 0.05
    frac: float = 0.5     # fraction of bytes kept by truncate
    id: str = ""          # label for logs/status ("" = rule-<index>)
    # runtime counters (status surface; calls counts MATCHING calls,
    # fires counts injections)
    calls: int = field(default=0, compare=False)
    fires: int = field(default=0, compare=False)

    def __post_init__(self):
        if self.action not in ACTIONS:
            raise ValueError(
                f"unknown chaos action {self.action!r}; one of {ACTIONS}")
        if not any(fnmatch.fnmatch(s, self.site) for s in SITES):
            raise ValueError(
                f"rule site {self.site!r} matches no known site "
                f"(see {sorted(SITES)})")
        if self.action in ("truncate", "corrupt") and not any(
                fnmatch.fnmatch(s, self.site) for s in DATA_SITES):
            raise ValueError(
                f"action {self.action!r} needs a data-bearing site "
                f"(one of {sorted(DATA_SITES)}); {self.site!r} matches none")
        if self.action == "drop" and not any(
                fnmatch.fnmatch(s, self.site) for s in DROP_SITES):
            raise ValueError(
                f"action 'drop' needs a droppable site (one of "
                f"{sorted(DROP_SITES)}); {self.site!r} matches none")
        if not 0.0 <= self.p <= 1.0:
            raise ValueError(f"rule p={self.p} outside [0, 1]")
        if self.nth < 0 or self.max_fires < 0:
            raise ValueError("nth / max_fires must be >= 0")


def _draw(seed: int, rule_idx: int, n: int) -> float:
    """Pure-hash uniform in [0, 1) for the n-th matching call of one
    rule: replayable regardless of thread interleaving."""
    h = hashlib.sha256(f"{seed}:{rule_idx}:{n}".encode()).digest()
    return int.from_bytes(h[:8], "big") / float(1 << 64)


class FaultPlane:
    """The process-wide rule registry + decision engine. Thread-safe;
    decisions happen under one lock, sleeps/wedges happen outside it."""

    def __init__(self, rules: list[FaultRule], seed: int = 0):
        self.rules = list(rules)
        for i, r in enumerate(self.rules):
            if not r.id:
                r.id = f"rule-{i}"
        self.seed = int(seed)
        self.t0 = time.monotonic()
        self.activated_unix = time.time()
        self._lock = threading.Lock()
        self._seq = 0
        self.log: deque = deque(maxlen=LOG_MAX)
        self._released = threading.Event()  # releases every wedge

    # ------------------------------------------------------------ decide
    def _decide(self, site: str, tenant: str, key: str) -> FaultRule | None:
        with self._lock:
            now = time.monotonic() - self.t0
            for i, r in enumerate(self.rules):
                if not fnmatch.fnmatchcase(site, r.site):
                    continue
                if r.tenant and not fnmatch.fnmatchcase(tenant, r.tenant):
                    continue
                if r.key and not fnmatch.fnmatchcase(key, r.key):
                    continue
                # data-shaped actions only match sites that can honor
                # them (a glob rule may span both kinds): a fired rule
                # must always have a real effect, or drills lie
                if r.action in ("truncate", "corrupt") and site not in DATA_SITES:
                    continue
                if r.action == "drop" and site not in DROP_SITES:
                    continue
                # the call counter ticks on every MATCHING call, before
                # window/cap checks: the draw sequence (and so replay)
                # depends only on the per-rule call sequence
                r.calls += 1
                n = r.calls
                if now < r.begin_s:
                    continue
                if r.for_s and now > r.begin_s + r.for_s:
                    continue
                if r.max_fires and r.fires >= r.max_fires:
                    continue
                if r.nth:
                    if n % r.nth:
                        continue
                elif r.p < 1.0 and _draw(self.seed, i, n) >= r.p:
                    continue
                r.fires += 1
                self._seq += 1
                self.log.append((self._seq, site, r.action, r.id, key))
                return r
        return None

    def _expired(self, r: FaultRule) -> bool:
        return bool(r.for_s) and (
            time.monotonic() - self.t0 > r.begin_s + r.for_s)

    # ------------------------------------------------------------- apply
    def _apply(self, r: FaultRule, site: str):
        """Execute a fired rule's action (outside the decision lock).
        Returns DROP for drop, None otherwise; raises for errors."""
        INJECTED.inc(labels=f'site="{site}",action="{r.action}"')
        if r.action == "latency":
            time.sleep(r.latency_s)
            return None
        if r.action == "drop":
            return DROP
        if r.action == "wedge":
            # hold the caller until release()/clear() or window expiry;
            # polled so an expired rule frees its captives on its own
            while not self._released.wait(0.05):
                if self._expired(r):
                    break
            return None
        if r.action == "error":
            raise _error_factory(r.error or _default_error(site))
        return r  # truncate/corrupt: caller applies _mangle to its data

    def _mangle(self, r: FaultRule, data: bytes) -> bytes:
        if not isinstance(data, (bytes, bytearray)) or not data:
            return data
        if r.action == "truncate":
            return bytes(data[: max(0, int(len(data) * r.frac))])
        # corrupt: deterministic single-byte flip keyed by the rule's
        # fire count (already advanced), so replays corrupt identically
        pos = (r.fires * 2654435761) % len(data)
        out = bytearray(data)
        out[pos] ^= 0xFF
        return bytes(out)

    # ----------------------------------------------------------- tapping
    def tap(self, site: str, tenant: str = "", key: str = ""):
        """Data-less tap: may sleep, raise, or return DROP."""
        r = self._decide(site, tenant, key)
        if r is None:
            return None
        out = self._apply(r, site)
        return DROP if out is DROP else None

    def call(self, site: str, fn, tenant: str = "", key: str = ""):
        """Wrap one data-producing operation: error/latency/wedge fire
        before `fn`, truncate/corrupt mangle its result, drop raises
        (an object read cannot be silently dropped)."""
        r = self._decide(site, tenant, key)
        if r is None:
            return fn()
        out = self._apply(r, site)
        if out is DROP:
            raise _error_factory(_default_error(site))
        if out is None:
            return fn()
        return self._mangle(r, fn())

    def mangle(self, site: str, data: bytes, tenant: str = "", key: str = ""):
        """Tap for seams that HOLD the bytes (WAL append): truncate /
        corrupt transform them, drop empties them, errors raise."""
        r = self._decide(site, tenant, key)
        if r is None:
            return data
        out = self._apply(r, site)
        if out is DROP:
            return b""
        if out is None:
            return data
        return self._mangle(r, data)

    # ---------------------------------------------------------- control
    def release(self) -> None:
        """Free every wedged caller (and any future wedge fires)."""
        self._released.set()

    def injection_log(self) -> list[tuple]:
        with self._lock:
            return list(self.log)

    def status(self) -> dict:
        from dataclasses import fields as dc_fields

        # show fields that DIFFER from the dataclass defaults (plus the
        # always-interesting core): "!= default", not "falsy" -- an
        # explicit latency_s=0.0 / frac=0.0 drill must not render
        # indistinguishably from the defaults
        defaults = {f.name: f.default for f in dc_fields(FaultRule)}
        core = ("site", "action", "p", "calls", "fires")
        with self._lock:
            rules = []
            for r in self.rules:
                d = {k: v for k, v in asdict(r).items()
                     if k in core or v != defaults.get(k)}
                rules.append(d)
            log = list(self.log)[-32:]
        return {
            "enabled": True,
            "seed": self.seed,
            "activated_unix": round(self.activated_unix, 3),
            "rules": rules,
            "injected_total": sum(r["fires"] for r in rules),
            "recent_injections": [
                {"seq": s, "site": site, "action": a, "rule": rid,
                 "key": k}
                for s, site, a, rid, k in log],
        }


# ------------------------------------------------------------ singleton
_plane: FaultPlane | None = None
_env_checked = False
_plane_lock = threading.Lock()


def _check_env_locked() -> None:
    global _plane, _env_checked
    _env_checked = True
    import os

    spec = os.environ.get(ENV, "")
    if spec:
        _plane = _plane_from_spec(spec)


def active() -> FaultPlane | None:
    """The live plane, arming lazily from TEMPO_CHAOS on first ask.
    The post-arming fast path is a plain attribute read."""
    if _env_checked:
        return _plane
    with _plane_lock:
        if not _env_checked:
            _check_env_locked()
        return _plane


def is_active() -> bool:
    return active() is not None


# --------------------------------------------------- module-level taps
def tap(site: str, tenant: str = "", key: str = ""):
    p = active()
    if p is None:
        return None
    return p.tap(site, tenant, key)


def call(site: str, fn, tenant: str = "", key: str = ""):
    p = active()
    if p is None:
        return fn()
    return p.call(site, fn, tenant, key)


def mangle(site: str, data: bytes, tenant: str = "", key: str = ""):
    p = active()
    if p is None:
        return data
    return p.mangle(site, data, tenant, key)


# ------------------------------------------------------- configuration
def parse_rules(doc) -> tuple[list[FaultRule], int]:
    """Normalize a rules document: a list of rule dicts, or
    {"seed": int, "rules": [...]}. Raises ValueError on anything the
    plane would not run."""
    seed = 0
    rules_doc = doc
    if isinstance(doc, dict):
        seed = int(doc.get("seed", 0))
        rules_doc = doc.get("rules", [])
    if not isinstance(rules_doc, list):
        raise ValueError('chaos rules must be a list (or {"seed", "rules"})')
    valid = {f for f in FaultRule.__dataclass_fields__
             if f not in ("calls", "fires")}
    rules = []
    for i, rd in enumerate(rules_doc):
        if not isinstance(rd, dict) or "site" not in rd:
            raise ValueError(f"chaos rule #{i} must be a dict with a 'site'")
        unknown = set(rd) - valid
        if unknown:
            raise ValueError(f"chaos rule #{i} has unknown fields "
                             f"{sorted(unknown)}")
        rules.append(FaultRule(**rd))
    return rules, seed


def _plane_from_spec(spec: str) -> FaultPlane:
    """Spec string -> plane: inline JSON, a path, or @path."""
    text = spec.strip()
    if not text.startswith(("[", "{")):
        path = text[1:] if text.startswith("@") else text
        with open(path) as f:
            text = f.read()
    rules, seed = parse_rules(json.loads(text))
    return FaultPlane(rules, seed=seed)


def configure(rules, seed: int = 0) -> FaultPlane:
    """Install a plane from already-parsed rules (dicts or FaultRules)."""
    global _plane, _env_checked
    parsed = [r if isinstance(r, FaultRule) else FaultRule(**r)
              for r in rules]
    with _plane_lock:
        if _plane is not None:
            _plane.release()
        _plane = FaultPlane(parsed, seed=seed)
        _env_checked = True
        return _plane


def configure_spec(spec: str) -> FaultPlane:
    """Install a plane from a spec string (inline JSON / path / @path)."""
    global _plane, _env_checked
    new = _plane_from_spec(spec)
    with _plane_lock:
        if _plane is not None:
            _plane.release()
        _plane = new
        _env_checked = True
        return _plane


def clear() -> None:
    """Tear the plane down (releasing wedges); taps become no-ops."""
    global _plane, _env_checked
    with _plane_lock:
        if _plane is not None:
            _plane.release()
        _plane = None
        _env_checked = True


def reset_for_tests() -> None:
    """Forget everything INCLUDING the lazy env check."""
    global _plane, _env_checked
    with _plane_lock:
        if _plane is not None:
            _plane.release()
        _plane = None
        _env_checked = False


def status() -> dict:
    p = active()
    if p is None:
        return {"enabled": False, "rules": [], "sites": sorted(SITES)}
    out = p.status()
    out["sites"] = sorted(SITES)
    return out


# ------------------------------------------------------------ metrics
def metrics_lines() -> list[str]:
    return INJECTED.text()


def help_entries() -> dict[str, str]:
    return {"tempo_chaos_injected": INJECTED.help}
