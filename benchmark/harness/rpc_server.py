"""Cells of a `trivy server` under CI clients (traffic kind
``lockfile_clients``): the program's server (``rpc/server.start_server``)
runs in the measuring process, which holds the chip, with the seed's
advisory DB loaded once; ``clients`` worker processes
(``dep_client.py``, CPU only) each run `trivy fs --scanners vuln --server`
over the seed's lockfile checkouts in a closed loop.

The window opens when every client has made its untimed warm-up request
and closes when the last request started before ``--seconds`` ends.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

from benchmark import common
from benchmark.reference.vulns import VulnReference
from benchmark.traffic.advisories import make_db
from benchmark.traffic.lockfiles import KINDS, lockfile_pool

CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "dep_client.py")


def _db(config: dict, seed: int) -> tuple[str, dict, dict]:
    """The seed's advisory DB, made anew in an emptied directory."""
    db_dir = common.fresh_dir(os.path.join(common.CACHE, "inputs", config["name"] + "-db"))
    buckets, names = make_db(db_dir, [seed, 7])
    return db_dir, buckets, names


class Server:
    """The program's scan server on a free localhost port; in a traced run
    it also keeps the spans and counters of each scan's trace context, as
    the server exports them (``ServerMetrics.observe_scan``)."""

    def __init__(self, db_dir: str, cache_dir: str, keep_traces: bool):
        from trivy_tpu.db import load_default_db
        from trivy_tpu.rpc.server import start_server

        db = load_default_db(db_dir, None)
        if db is None:
            raise RuntimeError(f"the program loaded no advisory DB from {db_dir}")
        self.httpd, self.port = start_server("127.0.0.1", 0, cache_dir=cache_dir, vuln_client=db)
        self.spans: dict[str, float] = {}
        self.counters: dict[str, int] = {}
        self.keep = False
        if keep_traces:
            metrics = self.httpd.service.metrics
            observe = metrics.observe_scan

            def observe_and_keep(ctx, seconds):
                observe(ctx, seconds)
                if self.keep:
                    for name, (total, _threads) in ctx.stage_totals().items():
                        self.spans[name] = self.spans.get(name, 0.0) + total
                    for name, v in dict(ctx.counters).items():
                        self.counters[name] = self.counters.get(name, 0) + v

            metrics.observe_scan = observe_and_keep
            # the benchmark's own span around each call into the server's
            # scan, so that the trace attributes device idle gaps to it
            service = self.httpd.service
            scan = service.scan

            def annotated_scan(*a, **kw):
                with common.annotate("bench.server_scan"):
                    return scan(*a, **kw)

            service.scan = annotated_scan

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()


def _start_clients(n: int, plan_of, procs: list) -> None:
    """Start the client processes together (each imports the program
    while the others do), then take them through their warm-up requests
    one at a time: the server's first concurrent requests race (PERF.md
    §7), and set-up is not what the window measures."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    for _c in range(n):
        procs.append(subprocess.Popen([sys.executable, "-u", CLIENT], stdin=subprocess.PIPE,
                                      stdout=subprocess.PIPE, text=True, env=env,
                                      cwd=common.ROOT))
    for c, p in enumerate(procs):
        p.stdin.write(json.dumps(plan_of(c)) + "\n")
        p.stdin.flush()
        ready = _line(p)
        if not ready.get("ready"):
            raise RuntimeError(f"client {c}'s warm-up request failed: {ready}")


def _line(p: subprocess.Popen) -> dict:
    """The next JSON line of a client; the program's own output on stdout
    (there is none with ``-o``) would be skipped."""
    while True:
        line = p.stdout.readline()
        if not line:
            raise RuntimeError(f"client {p.pid} ended (exit {p.wait()})")
        if line.startswith("{"):
            return json.loads(line)


def _stop(procs: list[subprocess.Popen]) -> None:
    for p in procs:
        if p.poll() is None:
            p.kill()
        p.wait()


def _report(path: str) -> tuple[set, set, bool]:
    """``(inventory, vulnerabilities, degraded)`` of one client report."""
    with open(path) as f:
        doc = json.load(f)
    inv, vulns = set(), set()
    for r in doc.get("Results") or []:
        for p in r.get("Packages") or []:
            if p.get("Version"):
                inv.add((p.get("Name"), p.get("Version")))
        for v in r.get("Vulnerabilities") or []:
            vulns.add((v.get("PkgName"), v.get("InstalledVersion"),
                       v.get("VulnerabilityID"), v.get("FixedVersion")))
    return inv, vulns, bool(doc.get("Degraded"))


def expected(ref: VulnReference, req: dict) -> tuple[set, set]:
    """The reference's inventory and verdicts for one request: the packages
    the generator wrote into the lockfile, and their vulnerabilities."""
    eco = KINDS[req["kind"]][1]
    inv = {(n, v) for n, v in req["packages"]}
    return inv, ref.vulnerable(eco, sorted(inv))


def read_answer(rc, out: str, errors: list) -> tuple[str | None, set, set]:
    """``(failure, inventory, vulnerabilities)`` of one client request: a
    failure where it errored, exited non-zero or reported Degraded."""
    if rc != 0 or errors:
        return f"exit {rc} {errors}", set(), set()
    try:
        inv, vulns, degraded = _report(out)
    except (OSError, ValueError) as e:
        return f"no report: {e}", set(), set()
    return ("report is Degraded" if degraded else None), inv, vulns


def compare(ref: VulnReference, answers: list, pool: list[dict]) -> dict:
    """The comparison that decides ``correct``: each answered request's
    inventory and verdicts (``answers``: ``[(index, failure, inventory,
    vulnerabilities)]``) against the reference's for the same request."""
    out = {"inventory_mismatch": 0, "vuln_mismatch": 0, "answered": 0, "packages": 0,
           "vulns_checked": 0, "failures": [], "examples": []}
    for index, failure, inv, vulns in answers:
        if failure:
            out["failures"].append(failure)
            continue
        out["answered"] += 1
        out["packages"] += len(pool[index]["packages"])
        want_inv, want_vulns = expected(ref, pool[index])
        out["vulns_checked"] += len(want_vulns)
        if inv != want_inv:
            out["inventory_mismatch"] += len(inv ^ want_inv)
            out["examples"].append(["inventory", index, sorted(inv ^ want_inv)[:3]])
        if vulns != want_vulns:
            out["vuln_mismatch"] += len(vulns ^ want_vulns)
            out["examples"].append(["vulns", index, sorted(vulns - want_vulns)[:2],
                                    sorted(want_vulns - vulns)[:2]])
    out["examples"] = out["examples"][:4]
    return out


def _checks(cmp: dict) -> list:
    return [("inventory_mismatch", cmp["inventory_mismatch"], 0),
            ("vuln_mismatch", cmp["vuln_mismatch"], 0)]


def run(bench: dict, cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        traced: bool, rehearse: bool, t_start: float, device: dict) -> tuple[dict, list]:
    if traffic["kind"] != "lockfile_clients":
        raise common.Refused(f"rpc_server drives lockfile_clients traffic, not {traffic['kind']!r}")
    n_clients = int(traffic["clients"])
    t0 = common.wall()
    db_dir, buckets, names = _db(config, seed)
    pool = lockfile_pool(common.inputs_dir(traffic), traffic, seed, names)
    n_warm = len(traffic["warmup"])
    if n_warm != n_clients:
        raise common.Refused("the traffic's warmup list names one kind per client")
    warm, pool = pool[len(pool) - n_warm:], pool[: len(pool) - n_warm]
    common.say("inputs", {"requests": len(pool), "packages": sum(len(r["packages"]) for r in pool),
                          "advisories": sum(len(a) for rows in buckets.values() for a in rows.values()),
                          "seconds": common.wall() - t0})
    work = common.fresh_dir(os.path.join(common.CACHE, "work", cell["name"]))
    server = Server(db_dir, os.path.join(work, "server-cache"), traced)
    argv = list(config["client_command"]) + ["--server", server.url]

    def plan_of(c: int) -> dict:
        return {"argv": argv + ["--cache-dir", os.path.join(work, f"client{c}", "cache")],
                "jobs": [[i, pool[i]["dir"]] for i in range(c, len(pool), n_clients)],
                "warmup": warm[c]["dir"], "work": os.path.join(work, f"client{c}")}

    procs: list[subprocess.Popen] = []
    try:
        _start_clients(n_clients, plan_of, procs)
        setup_s = common.wall() - t_start
        common.say("setup", {"setup_s": setup_s})
        log_dir = os.path.join(common.CACHE, "trace", cell["name"])
        server.keep = True
        with common.profiler_window(traced, log_dir) as prof:
            with common.annotate("bench.window"):
                go = time.time()
                for p in procs:
                    p.stdin.write(json.dumps({"deadline": go + seconds}) + "\n")
                    p.stdin.flush()
                with common.annotate("bench.server_under_load"):
                    done = [_line(p)["done"] for p in procs]
                window_end = max([r[2] for d in done for r in d] + [go])
        server.keep = False
    finally:
        _stop(procs)
    window_s = window_end - go
    peak = common.memory_peak_bytes(int(cell["chips"])) if not rehearse else 0
    server.close()

    reqs = [r for d in done for r in d]
    lat = [r[2] - r[1] for r in reqs]
    t_ref = common.wall()
    answers = [(index, *read_answer(rc, out, errors))
               for index, _s, _e, rc, out, errors in reqs]
    cmp = compare(VulnReference(buckets), answers, pool)
    pkgs = cmp["packages"]
    common.say("window", {"requests": len(reqs), "failed": len(cmp["failures"]),
                          "window_s": window_s, "packages": pkgs,
                          "distinct_requests": len({r[0] for r in reqs}),
                          "latency_samples": len(lat),
                          "latency_median_s": statistics.median(lat) if lat else None,
                          "failures": cmp["failures"][:3]})
    common.say("reference", {k: cmp[k] for k in ("inventory_mismatch", "vuln_mismatch",
                                                 "vulns_checked", "examples")}
               | {"seconds": common.wall() - t_ref})
    checks = _checks(cmp)
    body = {"correct": cmp["answered"] > 0 and common.correct(checks),
            "attempted": len(reqs), "failed": len(cmp["failures"])}
    device = dict(device, memory_peak_bytes=peak)
    if not traced:
        p95 = statistics.quantiles(lat, n=20, method="inclusive")[18] if len(lat) >= 2 else lat[0]
        body["metrics"] = {
            "dep_scan_pkgs_per_s": {"value": pkgs / window_s, "unit": "pkgs/s"},
            "dep_scan_p95_s": {"value": p95, "unit": "s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    else:
        readings = {"spans": server.spans, "counters": server.counters,
                    "packages": pkgs, "window_s": window_s}
        common.traced_result(bench, cell, body, device, readings, prof.get("xplane"), rehearse)
    body["device"] = device
    return body, checks


def coarse_compare(scheme: str, a: str, b: str) -> int:
    """The control's order: versions compared by their numeric release
    alone, pre-release tags dropped (the shortcut an integer encoding of
    these schemes would tempt)."""
    import re

    def core(v: str) -> tuple:
        nums = [int(x) for x in re.match(r"\d+(?:\.\d+)*", v.lstrip("vV")).group(0).split(".")]
        while len(nums) > 1 and nums[-1] == 0:
            nums.pop()
        return tuple(nums)

    ka, kb = core(a), core(b)
    return (ka > kb) - (ka < kb)


def control(bench: dict, cell: dict, config: dict, traffic: dict, seed: int,
            device: dict) -> tuple[dict, list]:
    """The control in the program's place: the reference with pre-release
    tags dropped from every version answers each request of the seed's
    pool (its inventory is the lockfile's packages), judged by the
    comparison and the rule of ``run``."""
    _db_dir, buckets, names = _db(config, seed)
    pool = lockfile_pool(common.inputs_dir(traffic), traffic, seed, names)
    pool = pool[: len(pool) - len(traffic["warmup"])]
    low = VulnReference(buckets, coarse_compare)
    answers = []
    for index, req in enumerate(pool):
        eco = KINDS[req["kind"]][1]
        inv = {(n, v) for n, v in req["packages"]}
        answers.append((index, None, inv, low.vulnerable(eco, sorted(inv))))
    cmp = compare(VulnReference(buckets), answers, pool)
    common.say("control", {k: cmp[k] for k in ("inventory_mismatch", "vuln_mismatch",
                                               "vulns_checked", "examples")})
    checks = _checks(cmp)
    return {"correct": cmp["answered"] > 0 and common.correct(checks),
            "attempted": len(answers)}, checks
