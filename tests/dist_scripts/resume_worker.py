"""Kill-a-rank → detect → checkpoint-resume recovery worker (round-3
verdict missing #2).

Reference mechanism: a dead ps-lite node is surfaced by
`KVStore::get_dead_nodes` and the restarted job rejoins with
`is_recovery` skipping barriers (`src/kvstore/kvstore_dist.h:52,138`);
SURVEY §5.3 prescribes checkpoint-restart + failure surfacing for the
TPU build.  This worker runs one of three phases of that story
(MODE env var), all over a 2-process × 2-device SPMD mesh:

  oracle : train 8 deterministic steps uninterrupted; record the loss
           trajectory + final weights.
  part1  : train with per-step checkpoints (params + optimizer states +
           step counter, rank 0).  Rank 1 kills itself (os._exit) after
           completing step 3; rank 0 detects it through the heartbeat
           liveness store (`get_dead_nodes`), writes a detection marker,
           and exits with code 3 — the launcher surfaces the failure.
  part2  : fresh processes resume from the checkpoint and train the
           remaining steps; the recorded trajectory must continue the
           oracle's exactly (asserted by tests/test_recovery.py).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ.setdefault("MXNET_HEARTBEAT_INTERVAL", "0.5")

import jax

# N local processes cannot share a chip: CPU-only whatever was inherited
jax.config.update("jax_platforms", "cpu")

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import gluon

TOTAL_STEPS = 8
KILL_AFTER_STEP = 3  # rank 1 dies once this step's update has landed


class WithLoss(gluon.block.HybridBlock):
    def __init__(self, n):
        super().__init__()
        self.n = n

    def forward(self, x, y):
        d = self.n(x) - y
        return (d * d).mean()


def build():
    """Deterministic model/trainer/data — identical in every phase."""
    from mxnet_tpu.parallel import mesh as pmesh

    mx.random.seed(5)
    net = gluon.nn.Dense(4, use_bias=True)
    net.initialize()
    mod = WithLoss(net)
    rs = onp.random.RandomState(21)
    data = [(rs.rand(16, 6).astype("f"), rs.rand(16, 4).astype("f"))
            for _ in range(TOTAL_STEPS)]
    mod(mx.np.array(data[0][0]), mx.np.array(data[0][1]))  # shapes
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="tpu_ici")
    mesh = pmesh.make_mesh({"dp": len(jax.devices())})
    step = gluon.FusedTrainStep(mod, trainer, mesh=mesh)
    return net, trainer, step, data


def save_ckpt(ckpt_dir, net, trainer, step_no):
    net.save_parameters(os.path.join(ckpt_dir, "net.params"))
    trainer.save_states(os.path.join(ckpt_dir, "trainer.states"))
    with open(os.path.join(ckpt_dir, "step.json.tmp"), "w") as f:
        json.dump({"step": step_no}, f)
    os.replace(os.path.join(ckpt_dir, "step.json.tmp"),
               os.path.join(ckpt_dir, "step.json"))


def run_steps(step, data, start, stop):
    losses = []
    for i in range(start, stop):
        x, y = data[i]
        loss = step(mx.np.array(x), mx.np.array(y), batch_size=1)
        losses.append(float(loss.asnumpy()))
    return losses


def main():
    mode = os.environ["MODE"]
    out_dir = os.environ["OUT_DIR"]
    rank = jax.process_index()
    assert jax.process_count() == 2
    net, trainer, step, data = build()

    if mode == "oracle":
        losses = run_steps(step, data, 0, TOTAL_STEPS)
        if rank == 0:
            with open(os.path.join(out_dir, "oracle.json"), "w") as f:
                json.dump({"losses": losses,
                           "weight": net.weight.data().asnumpy().tolist()},
                          f)
        print(f"rank {rank} ORACLE OK", flush=True)
        return 0

    if mode == "part1":
        import time
        losses = []
        for i in range(TOTAL_STEPS):
            x, y = data[i]
            loss = step(mx.np.array(x), mx.np.array(y), batch_size=1)
            losses.append(float(loss.asnumpy()))
            if rank == 0:
                save_ckpt(out_dir, net, trainer, i)
            if i == KILL_AFTER_STEP and rank == 1:
                # simulate a wedged/stalled worker: the training loop and
                # its liveness heartbeat stop, but the process lingers
                # (the realistic stall mode — an os._exit here would race
                # jax's own coordination-service teardown against OUR
                # detection path, which is the thing under test)
                print("rank 1 SIMULATED CRASH", flush=True)
                trainer.kvstore.close()  # heartbeat stops; stamp goes stale
                time.sleep(20)
                os._exit(1)
            if i == KILL_AFTER_STEP and rank == 0:
                # the peer is gone: surface it through the liveness store
                # instead of hanging in the next collective
                store = trainer.kvstore
                deadline = time.time() + 60
                dead = store.get_dead_nodes(timeout=3)
                while not dead and time.time() < deadline:
                    time.sleep(0.5)
                    dead = store.get_dead_nodes(timeout=3)
                assert dead == [1], dead
                with open(os.path.join(out_dir, "detected.json"), "w") as f:
                    json.dump({"dead": dead, "at_step": i,
                               "losses": losses}, f)
                print(f"rank 0 DEAD DETECTED {dead}", flush=True)
                sys.exit(3)  # job aborts; the launcher reports failure
        raise AssertionError("part1 should never finish all steps")

    if mode == "part2":
        with open(os.path.join(out_dir, "step.json")) as f:
            done_through = json.load(f)["step"]
        net.load_parameters(os.path.join(out_dir, "net.params"))
        trainer.load_states(os.path.join(out_dir, "trainer.states"))
        losses = run_steps(step, data, done_through + 1, TOTAL_STEPS)
        if rank == 0:
            with open(os.path.join(out_dir, "resumed.json"), "w") as f:
                json.dump({"start": done_through + 1, "losses": losses,
                           "weight": net.weight.data().asnumpy().tolist()},
                          f)
        print(f"rank {rank} RESUME OK", flush=True)
        return 0

    raise ValueError(mode)


if __name__ == "__main__":
    sys.exit(main() or 0)
