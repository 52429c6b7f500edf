"""Shared scaffolding for two-process jax.distributed tests: spawn the
same worker template as coordinator + worker on a free localhost port,
collect stdout, kill on timeout, assert clean exits."""
import os
import socket
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_two_process(worker_template: str, timeout: int = 300,
                    marker: str = "RESULT"):
    """Format `worker_template` with root/addr/pid for pids 0 and 1, run
    both, and return {pid: [token, ...]} parsed from stdout lines that
    start with `marker` (tokens exclude the marker itself)."""
    addr = f"127.0.0.1:{free_port()}"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # 1 CPU device per process
    procs = [subprocess.Popen(
        [sys.executable, "-c",
         worker_template.format(root=ROOT, addr=addr, pid=pid)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env) for pid in (0, 1)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, (out, err[-3000:])
    results = {}
    for rc, out, err in outs:
        for line in out.splitlines():
            if line.startswith(marker):
                parts = line.split()
                results[int(parts[1])] = parts[2:]
    assert set(results) == {0, 1}, outs
    return results
