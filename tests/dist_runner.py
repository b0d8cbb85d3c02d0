"""Subprocess entry for the distributed test (reference
``test_dist_base.py`` runner role, driven by PADDLE_* env vars)."""
import os
import sys

import numpy as np


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Executor, Scope
    from paddle_tpu.distributed import notify_complete
    from dist_model import batches, build, param_values

    role = os.environ["PADDLE_TRAINING_ROLE"]
    endpoints = os.environ["PADDLE_PSERVER_ENDPOINTS"].split(",")
    trainers = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    trainer_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))

    prog, startup, loss = build()
    t = fluid.DistributeTranspiler()
    t.transpile(trainer_id=trainer_id, program=prog,
                pservers=",".join(endpoints), trainers=trainers,
                sync_mode=True, startup_program=startup)

    scope = Scope()
    exe = Executor()
    if role == "PSERVER":
        ep = os.environ["PADDLE_CURRENT_ENDPOINT"]
        exe.run(t.get_startup_program(ep), scope=scope)
        exe.run(t.get_pserver_program(ep), scope=scope)
        return

    tp = t.get_trainer_program()
    # deterministic startup: every pserver is bound and listening before
    # the first send (ready-files when PADDLE_READY_DIR is set)
    fluid.distributed.wait_server_ready(endpoints)
    exe.run(startup, scope=scope)
    runner = exe
    if os.environ.get("DIST_TRAINER_MESH") == "1":
        # trainer-mesh + remote-pserver topology (the kube_gen_job.py
        # deployment): each trainer runs its compute segments over a
        # LOCAL device mesh (dp over the virtual CPU devices) while the
        # send/recv host ops sync grads with the remote pservers
        import jax
        runner = fluid.ParallelExecutor(
            loss_name=loss.name, main_program=tp, scope=scope,
            places=jax.devices())
    n_steps = int(os.environ.get("DIST_STEPS", "5"))
    bs_half = 4
    for x, y in batches(n_steps):
        half = slice(trainer_id * bs_half, (trainer_id + 1) * bs_half)
        if runner is exe:
            exe.run(tp, feed={"x": x[half], "y": y[half]},
                    fetch_list=[loss], scope=scope)
        else:
            runner.run(feed={"x": x[half], "y": y[half]},
                       fetch_list=[loss])
    out = os.environ.get("DIST_OUT")
    if out:
        np.savez(out, **param_values(prog, scope))
    notify_complete(endpoints, trainer_id=trainer_id)


if __name__ == "__main__":
    main()
