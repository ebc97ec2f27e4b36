"""The benchmark's one child: the program's normal single-node entry,
`opensearch_tpu.cli.main --node-name .. --http-port .. --data ..`, called
in-process so that the process that holds the chip can also start and stop
`jax.profiler` around part of the window (the program has no profiler hook,
and only the process that holds the chip can trace it) and report the
backend's peak memory.

Commands arrive as lines on stdin and are answered as lines on stdout
that start with `[launcher]`; when stdin closes (the harness is gone) the
process ends, so a run leaves nothing behind.

    trace_start <dir>   -> [launcher] trace_started
    trace_stop          -> [launcher] trace_stopped
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
from pathlib import Path


def say(msg: str) -> None:
    print(f"[launcher] {msg}", flush=True)


def control() -> None:
    import jax

    for line in sys.stdin:
        cmd = line.split()
        if not cmd:
            continue
        try:
            if cmd[0] == "trace_start":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(cmd[1], profiler_options=opts)
                say("trace_started")
            elif cmd[0] == "trace_stop":
                jax.profiler.stop_trace()
                say("trace_stopped")
            else:
                say(f"unknown command {cmd[0]}")
        except Exception as e:  # noqa: BLE001 - reported, the node serves on
            say(f"error {cmd[0]}: {type(e).__name__}: {e}")
    os._exit(0)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--http-port", type=int, required=True)
    ap.add_argument("--data", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    threading.Thread(target=control, daemon=True).start()
    from opensearch_tpu import cli

    return cli.main(["--node-name", "perf", "--http-port",
                     str(args.http_port), "--data", args.data])


if __name__ == "__main__":
    sys.exit(main())
