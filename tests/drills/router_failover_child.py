"""Child of test_router_failover.py: build the journal-armed socket
fleet through the REAL production path (``init_fleet`` detects the
journal, plans adoption, adopts), open the HTTP door, announce both on
stdout, then serve until killed. The parent SIGKILLs the first
incarnation mid-traffic (the crash the journal exists for) and reads the
second incarnation's announcement to pin the adoption.

    python tests/drills/router_failover_child.py '{"nodes": ..., "journal_dir": ...}'
"""

import json
import logging
import sys
import time

import deepspeed_tpu
from deepspeed_tpu.serving import HTTPDoor


def main():
    # stdout is the announce channel the parent parses: move the
    # package logger's stream handler to stderr so adoption log lines
    # cannot interleave with the JSON line
    for handler in logging.getLogger("DeepSpeedTPU").handlers:
        if isinstance(handler, logging.StreamHandler):
            handler.setStream(sys.stderr)
    spec = json.loads(sys.argv[1])
    router = deepspeed_tpu.init_fleet(nodes=spec["nodes"], config={
        "serving": {
            "backend": "socket",
            "journal": {"enabled": True, "dir": spec["journal_dir"]},
        },
    })
    door = HTTPDoor(router)
    host, port = door.start()
    snap = router.metrics.snapshot()
    print(json.dumps({
        "event": "serving", "host": host, "port": port,
        "adopted": int(snap.get("fleet/adopted_replicas", 0)),
    }), flush=True)
    while True:
        time.sleep(3600)


if __name__ == "__main__":
    main()
