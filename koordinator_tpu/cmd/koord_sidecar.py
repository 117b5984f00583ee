"""TPU scheduling sidecar binary: serve ScheduleBatch next to the chips.

The rebuild-specific sixth binary (SURVEY.md 5.8): a gRPC server wrapping
the fused full-chain kernel, consumed by the Python cycle driver
(--sidecar-address), by the reference's Go event loop, or by the C++
client (native/sidecar_client.cpp). Step functions cache per shape."""

from __future__ import annotations

import argparse
import sys
import threading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="koord-sidecar")
    ap.add_argument("--listen", default="unix:///tmp/koord-sidecar.sock",
                    help="gRPC bind address (unix:///path or host:port)")
    args = ap.parse_args(argv)

    from koordinator_tpu.scheduler.sidecar import serve_sidecar

    server = serve_sidecar(args.listen)
    print(f"koord-sidecar: serving ScheduleBatch on {args.listen}",
          file=sys.stderr)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        pass
    server.stop(0)
    return 0


if __name__ == "__main__":
    from koordinator_tpu.cmd import run_binary

    raise SystemExit(run_binary(main))
