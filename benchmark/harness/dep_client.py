"""One CI client of a ``rpc_server`` cell: a long-lived worker process that
never touches the chip (its parent starts it with ``JAX_PLATFORMS=cpu``)
and runs the program's client path, ``trivy_tpu.cli.main(["fs", ...,
"--server", URL, <checkout>])``, in a closed loop.

Protocol on stdin/stdout, one JSON object per line:
parent -> ``{"argv": [...], "jobs": [[index, dir], ...], "warmup": dir,
"work": dir}``; worker -> ``{"ready": true}`` after one untimed request;
parent -> ``{"deadline": <epoch seconds>}``; worker -> ``{"done": [[index,
start, end, rc, out, errors], ...]}`` once it has started no request after
the deadline and its last one has ended.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import time


class _Contained(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage())


def _request(argv: list[str], checkout: str, out: str) -> tuple[object, list]:
    from trivy_tpu import cli

    contained = _Contained()
    watched = logging.getLogger("trivy_tpu.analyzer")
    watched.addHandler(contained)
    try:
        rc = cli.main(argv + ["-o", out, checkout])
    except Exception as e:  # a request that raises is a failed request
        rc = f"raised {type(e).__name__}: {e}"
    finally:
        watched.removeHandler(contained)
    return rc, contained.messages


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    from trivy_tpu import cli  # noqa: F401  (imported before the plan comes)

    plan = json.loads(sys.stdin.readline())
    argv, work = plan["argv"], plan["work"]
    os.makedirs(work, exist_ok=True)
    rc, errors = _request(argv, plan["warmup"], os.path.join(work, "warmup.json"))
    print(json.dumps({"ready": rc == 0 and not errors, "rc": str(rc), "errors": errors[:3]}), flush=True)
    deadline = json.loads(sys.stdin.readline())["deadline"]
    done = []
    k = 0
    jobs = plan["jobs"]
    while time.time() < deadline:
        index, checkout = jobs[k % len(jobs)]
        out = os.path.join(work, f"out{k}.json")
        t0 = time.time()
        rc, errors = _request(argv, checkout, out)
        done.append([index, t0, time.time(), rc if isinstance(rc, int) else str(rc), out, errors[:3]])
        k += 1
    print(json.dumps({"done": done}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
