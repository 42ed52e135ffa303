"""Two-PROCESS multi-host serving demo (ref: MultiNodeConfig engines.rs:28).

Everything here is the real production path, exercised across actual OS
processes rather than simulated in one:

  parent ──spawns──► control-plane broker (python -m dynamo_tpu.control_plane)
         ──spawns──► worker rank? ┐ DYN_CONTROL_PLANE=tcp
         ──spawns──► worker rank? ┘ (ranks assigned by store rendezvous)

Each worker connects a DistributedRuntime to the broker, wins a rank via
``multihost.rendezvous`` (create-only store puts), joins the jax
multi-controller runtime (``jax.distributed.initialize`` — rank 0's
coordinator address travels through the control plane), builds ONE global
dp×tp mesh over both processes' devices (dp crosses the process/DCN
boundary, tp stays inside), shards real llama params + paged KV over it,
and executes the same sharded decode step SPMD. CPU backend with 4
virtual devices per process → an 8-device global mesh, per the repo's
multi-chip testing convention.

Prints ONE JSON line.

Usage: python tools/demo_multihost.py
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Run as a file from a bare checkout (parent and --as-worker children alike).
sys.path.insert(0, REPO)
GROUP = "demo2p"
NPROC = 2
LOCAL_DEVICES = 4


def _worker() -> None:
    import asyncio

    async def main():
        from dynamo_tpu.engine.multihost import init_multihost, rendezvous
        from dynamo_tpu.runtime.distributed import DistributedRuntime

        import jax

        drt = await DistributedRuntime.from_settings()
        mh = await rendezvous(drt, GROUP, NPROC)
        init_multihost(mh)  # joins the jax multi-controller runtime
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dynamo_tpu.engine.config import get_config
        from dynamo_tpu.engine.models import llama
        from dynamo_tpu.engine.multihost import build_multihost_mesh
        from dynamo_tpu.engine.sharding import ParallelConfig, kv_cache_spec, param_specs

        assert jax.device_count() == NPROC * LOCAL_DEVICES, jax.device_count()
        par = ParallelConfig(tp=LOCAL_DEVICES)
        mesh = build_multihost_mesh(par, dcn_dp=NPROC)  # dp crosses processes

        cfg = get_config("tiny")
        specs = param_specs(cfg.tie_word_embeddings)
        p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
        params = jax.jit(
            lambda: llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32),
            out_shardings=p_sh,
        )()

        B, blocks, width = 4, 16, 8
        kv_sh = NamedSharding(mesh, kv_cache_spec(cfg.num_kv_heads, par.tp))
        bt_sh = NamedSharding(mesh, P("dp"))
        shape = (cfg.num_layers, blocks, cfg.block_size, cfg.num_kv_heads * cfg.head_dim)
        k0, v0, toks, pos, tables, active = jax.jit(
            lambda: (
                jnp.zeros(shape, jnp.float32),
                jnp.zeros(shape, jnp.float32),
                jnp.ones((B,), jnp.int32) * 5,
                jnp.ones((B,), jnp.int32) * 20,
                jnp.tile(jnp.arange(1, width + 1, dtype=jnp.int32)[None], (B, 1)),
                jnp.ones((B,), bool),
            ),
            out_shardings=(kv_sh, kv_sh, bt_sh, bt_sh, bt_sh, bt_sh),
        )()

        @jax.jit
        def step(p, k, v, t, pos, bt, act):
            logits, k2, v2 = llama.decode(p, cfg, k, v, t, pos, bt, act)
            return jnp.sum(logits.astype(jnp.float32)), k2, v2

        s, k1, v1 = step(params, k0, v0, toks, pos, tables, active)
        s2, _, _ = step(params, k1, v1, toks, pos + 1, tables, active)
        result = {
            "process": mh.process_id,
            "num_processes": mh.num_processes,
            "coordinator": mh.coordinator,
            "global_devices": jax.device_count(),
            "local_devices": jax.local_device_count(),
            "mesh": {ax: int(n) for ax, n in mesh.shape.items()},
            "logits_sum_step1": float(s),
            "logits_sum_step2": float(s2),
        }
        print("MULTIHOST_WORKER " + json.dumps(result), flush=True)
        await drt.shutdown()

    asyncio.run(main())


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main() -> None:
    port = _free_port()
    broker = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.control_plane", "--host", "127.0.0.1", "--port", str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=REPO,
    )
    try:
        # Wait for the broker to listen.
        deadline = time.time() + 20
        up = False
        while time.time() < deadline:
            try:
                with socket.create_connection(("127.0.0.1", port), timeout=0.5):
                    up = True
                    break
            except OSError:
                time.sleep(0.2)
        if not up:
            broker.kill()
            out, _ = broker.communicate(timeout=10)
            raise RuntimeError(f"control-plane broker never listened: {out.strip()[-400:]}")

        env = dict(os.environ)
        env.update({
            "DYN_CONTROL_PLANE": "tcp",
            "DYN_CONTROL_PLANE_ADDRESS": f"127.0.0.1:{port}",
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={LOCAL_DEVICES}",
        })
        workers = [
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--as-worker"],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=REPO,
            )
            for _ in range(NPROC)
        ]
        results = []
        ok = True
        try:
            for w in workers:
                out, _ = w.communicate(timeout=240)
                found = None
                for line in out.splitlines():
                    if line.startswith("MULTIHOST_WORKER "):
                        found = json.loads(line[len("MULTIHOST_WORKER "):])
                if found is None or w.returncode != 0:
                    ok = False
                    results.append({"rc": w.returncode, "tail": out.strip()[-400:]})
                else:
                    results.append(found)
        finally:
            for w in workers:
                if w.poll() is None:
                    w.kill()

        sums = {(r.get("logits_sum_step1"), r.get("logits_sum_step2")) for r in results if "process" in r}
        all_ok = ok and len([r for r in results if "process" in r]) == NPROC
        artifact = {
            "ok": all_ok and len(sums) == 1,
            "processes": NPROC,
            "local_devices_per_process": LOCAL_DEVICES,
            # Only meaningful when every worker completed; a lone survivor
            # must not read as a verified cross-process comparison.
            "spmd_results_identical": all_ok and len(sums) == 1,
            "workers": results,
        }
        print(json.dumps(artifact))
        sys.exit(0 if artifact["ok"] else 1)
    finally:
        broker.terminate()


if __name__ == "__main__":
    if "--as-worker" in sys.argv:
        _worker()
    else:
        main()
