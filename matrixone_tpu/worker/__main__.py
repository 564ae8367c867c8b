"""Worker process entry: `python -m matrixone_tpu.worker [--port P]`.

Reference analogue: `cmd/mo-service/main.go:448 startPythonUdfService` —
the accelerator worker as its own service role. Prints `PORT <n>` so a
parent coordinator (or test) spawning with --port 0 can discover the bound
port.
"""

import argparse
import sys
import time

from matrixone_tpu.worker.server import TpuWorkerServer


def main() -> None:
    from matrixone_tpu.utils import enable_compilation_cache, motrace
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args()
    motrace.TRACER.proc = "worker"
    srv = TpuWorkerServer(port=args.port).start()
    print(f"PORT {srv.port}", flush=True)
    sys.stdout.flush()
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()


if __name__ == "__main__":
    main()
