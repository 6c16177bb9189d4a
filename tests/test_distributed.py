"""Distribution-layer tests: sharding rules, compression, fault tolerance.

Mesh-dependent tests run in a subprocess with 8 forced host devices so the
main test process keeps the real (1-device) topology.
"""
import json
import subprocess
import sys
import textwrap
import time

import numpy as np

from repro.distributed.fault_tolerance import HeartbeatMonitor


def _run_subprocess(code: str) -> str:
    """Run code with 8 fake devices; return stdout."""
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            + textwrap.dedent(code))
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__('os').environ,
                              "PYTHONPATH": "src"})
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharding_rules_divisibility_degrade():
    out = _run_subprocess("""
    import jax, json
    from repro.distributed import use_sharding
    from repro.distributed.sharding import param_shardings
    from repro.models import Model
    from repro.configs import get_config

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    results = {}
    for arch in ("qwen2-0.5b", "olmoe-1b-7b", "grok-1-314b"):
        model = Model(get_config(arch))
        with use_sharding(mesh) as ctx:
            shards = param_shardings(ctx, model.abstract_params())
        if arch == "qwen2-0.5b":
            # merged q dim 896 divisible by 4 -> TP; embed vocab TP
            results["qwen2_wq"] = str(shards["blocks"]["attn"]["wq"].spec)
            results["qwen2_embed"] = str(shards["embed"].spec)
        else:
            # olmoe: 64 experts % 4 == 0 -> expert parallel
            # grok: 8 experts % 4 == 0 too at tp=4; d_ff gets nothing
            results[arch.split("-")[0] + "_wgate"] = \
                str(shards["blocks"]["mlp"]["w_gate"].spec)
    print(json.dumps(results))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert "model" in res["qwen2_wq"]          # TP applied
    assert "data" in res["qwen2_wq"]           # FSDP applied
    assert res["olmoe_wgate"].startswith("PartitionSpec(None, 'model'")
    assert res["grok_wgate"].startswith("PartitionSpec(None, 'model'")


def test_grok_expert_fallback_at_tp16():
    """At TP=8 (> n_experts would not divide), grok-1's 8 experts divide 8,
    but with mesh model=3 they cannot -> TP inside experts instead."""
    out = _run_subprocess("""
    import jax, json
    from repro.distributed import use_sharding
    from repro.distributed.sharding import param_shardings
    from repro.models import Model
    from repro.configs import get_config

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # force non-dividing expert count by lying about experts: use olmoe with
    # 64 -> divides; emulate grok-at-16 with a reduced config instead
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("grok-1-314b").with_(n_experts=6)  # 6 % 4 != 0
    model = Model(cfg)
    with use_sharding(mesh) as ctx:
        shards = param_shardings(ctx, model.abstract_params())
    print(json.dumps({"wgate": str(shards["blocks"]["mlp"]["w_gate"].spec)}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    # experts degraded -> d_ff picks up "model" (TP inside experts)
    assert res["wgate"] == "PartitionSpec(None, None, 'data', 'model')"


def test_compressed_cross_pod_reduction():
    out = _run_subprocess("""
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.compression import make_pod_compressed_grad_fn

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)

    def loss(w, batch):
        x, y = batch["x"], batch["y"]
        pred = x @ w
        return jnp.mean((pred - y) ** 2)

    w = jnp.ones((16, 4), jnp.float32)
    x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16) / 100
    y = jnp.ones((8, 4), jnp.float32)
    grad_fn = make_pod_compressed_grad_fn(loss, mesh)
    with jax.set_mesh(mesh):
        xb = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"))))
        yb = jax.device_put(y, NamedSharding(mesh, P(("pod", "data"))))
        l, g = jax.jit(grad_fn)(w, {"x": xb, "y": yb})
    # reference: plain global gradient
    lr, gr = jax.value_and_grad(loss)(w, {"x": x, "y": y})
    rel = float(np.max(np.abs(np.asarray(g) - np.asarray(gr)))
                / (np.max(np.abs(np.asarray(gr))) + 1e-9))
    print(json.dumps({"rel_err": rel, "loss_match":
                      abs(float(l) - float(lr)) < 1e-5}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["loss_match"]
    assert res["rel_err"] < 0.02       # int8 quantization noise only


def test_elastic_reshard_across_meshes():
    out = _run_subprocess("""
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.fault_tolerance import elastic_reshard

    devs = jax.devices()
    mesh8 = jax.make_mesh((8,), ("data",),
                          axis_types=(jax.sharding.AxisType.Auto,))
    mesh4 = jax.sharding.Mesh(np.array(devs[:4]), ("data",))
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    x8 = jax.device_put(x, NamedSharding(mesh8, P("data")))
    x4 = elastic_reshard(x8, NamedSharding(mesh4, P("data")))
    ok = bool(np.array_equal(np.asarray(x4), np.asarray(x)))
    n_shards = len(x4.addressable_shards)
    print(json.dumps({"ok": ok, "n_shards": n_shards}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ok"] and res["n_shards"] == 4


# ------------------------------------------------------- fault tolerance
def test_heartbeat_detects_straggler_and_death():
    mon = HeartbeatMonitor(n_hosts=3, interval=0.05)
    transitions = []
    mon.on_transition(lambda h, old, new: transitions.append((h, old, new)))
    mon.start()
    time.sleep(0.4)
    assert all(s == "alive" for s in mon.statuses().values()), mon.statuses()

    mon.set_behavior(1, "straggler")
    time.sleep(0.8)
    # a straggler's beats are late every cycle: the monitor must have flagged
    # it at least once (status flaps back to alive when the late beat lands)
    assert any(h == 1 and new == "straggler" for h, _, new in transitions), \
        transitions

    mon.set_behavior(2, "dead")
    time.sleep(0.5)
    assert mon.statuses()[2] == "dead"
    assert any(h == 2 and new == "dead" for h, _, new in transitions)
    mon.stop()


def test_supervisor_restores_latest(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.training import CheckpointManager
    from repro.distributed.fault_tolerance import TrainSupervisor

    mgr = CheckpointManager(str(tmp_path))
    sup = TrainSupervisor(mgr, save_every=2)
    state = {"w": jnp.ones((4,))}
    sup.maybe_save(2, state)
    sup.finalize(3, {"w": jnp.full((4,), 3.0)})
    target = {"w": jax.ShapeDtypeStruct((4,), jnp.float32)}
    step, restored = sup.startup(lambda: state, target)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.full((4,), 3.0))
    mgr.close()
