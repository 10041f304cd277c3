"""Per-rule / per-bucket cost attribution for one scan ("scan profile").

Stall attribution (:mod:`trivy_tpu.obs.stall`) says *which stage* of a
pipeline is slow; this module says *which rule* and *which dispatch bucket*
— the difference between "confirm-bound 40%" and "confirm-bound 40%,
of which `aws-secret-access-key` burns 31% confirming device hits that the
exact host engine rejects". The batched-NFA design makes this essential:
one pathological rule (a hot keyword gate with a high host-confirm
false-positive rate) can dominate device time and confirm stalls while
staying invisible in per-stage totals.

Recorded per rule id:

- ``gate_hits`` — device prefilter hits ((row, rule) pairs the kernel
  flagged, including rows served from the dedup hit cache: a cached hit is
  still a logical device hit that will cost a confirm)
- ``confirms`` / ``confirm_s`` — exact host confirmations run for the rule
  and their wall time (on the CPU backend and the degraded host-fallback
  path this is the full rule evaluation, so a degraded scan still produces
  a complete profile)
- ``findings`` — locations that survived confirmation
- ``wasted_confirms`` / ``wasted_confirm_s`` — confirms that produced zero
  findings: pure false-positive cost. ``fp_rate`` = wasted / confirms is
  the gate false-positive rate the bucket-ladder and keyword-gate tuning
  rounds need.

Recorded per dispatch bucket (the batch-shape ladder — ``"1024"``,
``"512"``, ... for the secret pipeline; ``"license.gate:64"`` /
``"license.score:64"`` for the license corpus shards): dispatches, rows,
and blocking device-wait seconds, so the ladder is tunable from data
instead of folklore.

A :class:`ScanProfile` lives on a :class:`trivy_tpu.obs.TraceContext`
(created lazily via ``ctx.profile()``); serialized profiles (the ``Trace``
block of a remote scan response, or a saved ``--profile-out`` file) fold
into another profile with :meth:`ScanProfile.merge_dict`, which is how the
client merges its own pipeline profile with the server's.
"""

from __future__ import annotations

import os
import threading

# per-scan bound on rule label cardinality exported to Prometheus and the
# report table; the full profile still lands in --profile-out


def _topk_from_env() -> int:
    try:
        return max(1, int(os.environ.get("TRIVY_TPU_PROFILE_TOPK", "10")))
    except ValueError:
        return 10


TOP_K = _topk_from_env()

# internal per-rule slots: gate_hits, confirms, confirm_s, findings,
# wasted_confirms, wasted_confirm_s, prefilter_hits
_R = 7


class ScanProfile:
    """Thread-safe per-rule and per-bucket accumulators for one scan."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rules: dict[str, list] = {}
        self._buckets: dict[str, list] = {}  # key -> [dispatches, rows, wait_s]
        # keyword-candidate totals of the rule pass: [rows inspected, rows
        # with >=1 candidate rule]
        self._pre = [0, 0]
        # fleet cost attribution: per-shard rows (replica, bytes, wall,
        # steal/speculation provenance, attempts) + the per-replica
        # efficiency verdict the coordinator computes at fan-out end
        self._shards: list[dict] = []
        self._fleet_replicas: dict[str, dict] = {}

    def __bool__(self) -> bool:
        with self._lock:
            return bool(self._rules or self._buckets or self._shards
                        or self._fleet_replicas)

    def _rule(self, rule_id: str) -> list:
        r = self._rules.get(rule_id)
        if r is None:
            r = self._rules[rule_id] = [0, 0, 0.0, 0, 0, 0.0, 0]
        return r

    # -- recording ----------------------------------------------------------

    def gate_hit(self, rule_id: str, n: int = 1) -> None:
        """The device matcher flagged ``rule_id`` on ``n`` rows."""
        with self._lock:
            self._rule(rule_id)[0] += n

    def prefilter_hit(self, rule_id: str, n: int = 1) -> None:
        """The keyword prefilter made ``rule_id`` a candidate on ``n``
        rows. Per-rule candidate selectivity = prefilter_hits / the scan's
        prefiltered row total — the signal that says which rules' keywords
        are too common to gate anything."""
        with self._lock:
            self._rule(rule_id)[6] += n

    def prefilter_rows(self, rows: int, hit_rows: int = 0) -> None:
        """The rule pass inspected ``rows`` more rows for keywords, of
        which ``hit_rows`` carried at least one candidate rule."""
        with self._lock:
            self._pre[0] += rows
            self._pre[1] += hit_rows

    def confirm(self, rule_id: str, seconds: float, findings: int) -> None:
        """One exact host evaluation of ``rule_id`` took ``seconds`` and
        yielded ``findings`` surviving locations."""
        with self._lock:
            r = self._rule(rule_id)
            r[1] += 1
            r[2] += seconds
            r[3] += findings
            if findings == 0:
                r[4] += 1
                r[5] += seconds

    def bucket_dispatch(self, bucket, rows: int, wait_s: float) -> None:
        """One device dispatch of ``rows`` live rows in shape-bucket
        ``bucket`` spent ``wait_s`` in the blocking result fetch."""
        key = str(bucket)
        with self._lock:
            b = self._buckets.get(key)
            if b is None:
                b = self._buckets[key] = [0, 0, 0.0]
            b[0] += 1
            b[1] += rows
            b[2] += wait_s

    def note_shard(self, replica: str, nbytes: int, wall_s: float,
                   stolen: bool = False, speculated: bool = False,
                   attempts: int = 1) -> None:
        """One completed fleet shard's cost row: which replica ran it,
        how many planned bytes it covered, its winning-attempt wall time,
        and how it got there (stolen from a peer's queue / a speculative
        twin / after ``attempts - 1`` retries)."""
        with self._lock:
            self._shards.append({
                "replica": replica,
                "bytes": int(nbytes),
                "wall_ms": round(wall_s * 1e3, 3),
                "stolen": bool(stolen),
                "speculated": bool(speculated),
                "attempts": int(attempts),
            })

    def note_fleet(self, replicas: dict[str, dict]) -> None:
        """Attach the coordinator's per-replica efficiency verdict:
        ``{host: {"busy": %, "idle": %, "stalled_on_coordinator": %,
        "dead": %, ...}}`` — the four buckets sum to 100 per replica."""
        with self._lock:
            self._fleet_replicas.update(replicas)

    def merge_dict(self, doc: dict) -> None:
        """Fold a serialized profile (:meth:`to_dict` output) into this one
        — used to merge a remote scan's profile into the client's."""
        pre = doc.get("prefilter") or {}
        if pre:
            with self._lock:
                rows = int(pre.get("rows", 0))
                self._pre[0] += rows
                self._pre[1] += int(
                    pre.get("hit_rows", round(pre.get("selectivity", 0.0) * rows))
                )
        for rid, f in (doc.get("rules") or {}).items():
            with self._lock:
                r = self._rule(rid)
                r[0] += int(f.get("gate_hits", 0))
                r[1] += int(f.get("confirms", 0))
                r[2] += float(f.get("confirm_ms", 0.0)) / 1e3
                r[3] += int(f.get("findings", 0))
                r[4] += int(f.get("wasted_confirms", 0))
                r[5] += float(f.get("wasted_confirm_ms", 0.0)) / 1e3
                r[6] += int(f.get("prefilter_hits", 0))
        for key, bf in (doc.get("buckets") or {}).items():
            with self._lock:
                b = self._buckets.get(key)
                if b is None:
                    b = self._buckets[key] = [0, 0, 0.0]
                b[0] += int(bf.get("dispatches", 0))
                b[1] += int(bf.get("rows", 0))
                b[2] += float(bf.get("device_wait_ms", 0.0)) / 1e3
        fleet = doc.get("fleet") or {}
        if fleet:
            with self._lock:
                self._shards.extend(fleet.get("shards") or [])
                self._fleet_replicas.update(fleet.get("replicas") or {})

    # -- serialization ------------------------------------------------------

    def to_dict(self, top_k: int | None = None) -> dict:
        """JSON-serializable profile; rules ordered hottest-first (confirm
        time, then gate hits). ``top_k`` bounds the rule list for embedded
        copies (bench reps); None keeps every rule."""
        with self._lock:
            rules = {k: list(v) for k, v in self._rules.items()}
            buckets = {k: list(v) for k, v in self._buckets.items()}
            pre_rows, pre_hit_rows = self._pre
            shards = [dict(s) for s in self._shards]
            fleet_replicas = {k: dict(v)
                              for k, v in self._fleet_replicas.items()}
        items = sorted(rules.items(), key=lambda kv: (-kv[1][2], -kv[1][0], kv[0]))
        if top_k is not None:
            items = items[:top_k]
        doc = {
            "rules": {
                rid: {
                    "gate_hits": g,
                    "confirms": c,
                    "confirm_ms": round(cs * 1e3, 3),
                    "findings": f,
                    "wasted_confirms": wc,
                    "wasted_confirm_ms": round(wcs * 1e3, 3),
                    "fp_rate": round(wc / c, 4) if c else 0.0,
                    "prefilter_hits": p,
                    # per-rule candidate selectivity: what fraction of all
                    # prefiltered rows this rule's keywords flagged
                    "prefilter_selectivity": (
                        round(p / pre_rows, 6) if pre_rows else 0.0
                    ),
                }
                for rid, (g, c, cs, f, wc, wcs, p) in items
            },
            "buckets": {
                k: {
                    "dispatches": d,
                    "rows": rows,
                    "device_wait_ms": round(s * 1e3, 3),
                }
                for k, (d, rows, s) in sorted(buckets.items())
            },
        }
        if shards or fleet_replicas:
            doc["fleet"] = {
                "shards": shards,
                "replicas": fleet_replicas,
            }
        if pre_rows:
            doc["prefilter"] = {
                "rows": pre_rows,
                "hit_rows": pre_hit_rows,
                # scan-level selectivity: fraction of rows carrying >=1
                # candidate rule — the knob the smoke gate sanity-checks
                "selectivity": round(pre_hit_rows / pre_rows, 6),
            }
        return doc


def top_rules(doc: dict, k: int | None = None) -> list[tuple[str, dict]]:
    """Hottest rules of a serialized profile: by confirm time, then gate
    hits. ``k`` defaults to the TOP_K export bound."""
    items = sorted(
        (doc.get("rules") or {}).items(),
        key=lambda kv: (-kv[1].get("confirm_ms", 0.0), -kv[1].get("gate_hits", 0), kv[0]),
    )
    return items[: TOP_K if k is None else k]


def fleet_table_lines(doc: dict) -> list[str]:
    """Formatted fleet efficiency verdict for the --trace report: one row
    per replica with shard count, bytes, and the four 100%-sum buckets
    (``busy`` scanning shards / ``idle`` waiting for work / ``stalled``
    on the coordinator's tail / ``dead`` behind an open breaker)."""
    fleet = doc.get("fleet") or {}
    replicas = fleet.get("replicas") or {}
    if not replicas:
        return []
    per_host: dict[str, list] = {}  # host -> [shards, bytes, wall_ms]
    for row in fleet.get("shards") or []:
        agg = per_host.setdefault(row.get("replica", "?"), [0, 0, 0.0])
        agg[0] += 1
        agg[1] += int(row.get("bytes", 0))
        agg[2] += float(row.get("wall_ms", 0.0))
    lines = [
        f"{'replica':<28}{'shards':>7}{'MB':>9}{'busy%':>7}{'idle%':>7}"
        f"{'stall%':>7}{'dead%':>6}"
    ]
    for host in sorted(replicas):
        v = replicas[host]
        shards, nbytes, _ = per_host.get(host, [0, 0, 0.0])
        lines.append(
            f"{host:<28}{shards:>7}{nbytes / 1e6:>9.1f}"
            f"{v.get('busy', 0.0):>7.1f}{v.get('idle', 0.0):>7.1f}"
            f"{v.get('stalled_on_coordinator', 0.0):>7.1f}"
            f"{v.get('dead', 0.0):>6.1f}"
        )
    return lines


def table_lines(doc: dict, k: int | None = None) -> list[str]:
    """Formatted top-K "hottest rules" table for the --trace report."""
    rows = top_rules(doc, k)
    if not rows:
        return []
    lines = [
        f"{'rule':<34}{'gate_hits':>10}{'confirms':>9}{'confirm':>10}"
        f"{'fp%':>7}{'wasted':>10}{'found':>6}"
    ]
    for rid, f in rows:
        lines.append(
            f"{rid:<34}{f.get('gate_hits', 0):>10}{f.get('confirms', 0):>9}"
            f"{f.get('confirm_ms', 0.0):>8.1f}ms"
            f"{100.0 * f.get('fp_rate', 0.0):>6.1f}%"
            f"{f.get('wasted_confirm_ms', 0.0):>8.1f}ms"
            f"{f.get('findings', 0):>6}"
        )
    return lines
