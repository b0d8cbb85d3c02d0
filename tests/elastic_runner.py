"""Subprocess entry for the elastic re-discovery test: pserver and
trainer roles against a registry (distributed/registry.py), driven by
PADDLE_*/ELASTIC_* env vars.  The pserver role honors ELASTIC_BIND to
come back on a fresh port under the same logical endpoint — the
go/pserver etcd re-claim scenario."""
import json
import os
import sys

import numpy as np


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Executor, Scope
    from paddle_tpu.distributed import notify_complete
    from paddle_tpu.distributed.transpiler import DistributeTranspilerConfig
    from dist_model import batches, build

    role = os.environ["PADDLE_TRAINING_ROLE"]
    endpoints = os.environ["PADDLE_PSERVER_ENDPOINTS"].split(",")

    prog, startup, loss = build(lr=0.05)
    cfg = DistributeTranspilerConfig()
    cfg.checkpoint_dir = os.environ.get("ELASTIC_CKPT_DIR") or None
    cfg.checkpoint_every_rounds = 1
    t = fluid.DistributeTranspiler(config=cfg)
    t.transpile(trainer_id=0, program=prog, pservers=",".join(endpoints),
                trainers=1, sync_mode=False, startup_program=startup)

    scope = Scope()
    exe = Executor()
    if role == "PSERVER":
        ep = os.environ["PADDLE_CURRENT_ENDPOINT"]
        exe.run(t.get_startup_program(ep), scope=scope)
        ps_prog = t.get_pserver_program(ep)
        bind = os.environ.get("ELASTIC_BIND")
        if bind:
            for op in ps_prog.global_block.ops:
                if op.type == "listen_and_serv":
                    op.attrs["bind_endpoint"] = bind
        exe.run(ps_prog, scope=scope)
        return

    tp = t.get_trainer_program()
    exe.run(startup, scope=scope)
    from paddle_tpu.core import compile_cache
    if compile_cache.enabled():
        # elastic rejoin: hydrate the device-segment executables from
        # the persistent compile cache before the first batch — a
        # respawned trainer skips the XLA recompile
        bx, by = batches(1)[0]
        exe.warm_start(tp, feed_specs={"x": bx, "y": by},
                       fetch_list=[loss], scope=scope)
    n_steps = int(os.environ.get("DIST_STEPS", "30"))
    progress_path = os.environ["ELASTIC_PROGRESS"]
    losses = []
    for i, (x, y) in enumerate(batches(n_steps)):
        (l,) = exe.run(tp, feed={"x": x, "y": y}, fetch_list=[loss],
                       scope=scope)
        losses.append(float(np.asarray(l)))
        with open(progress_path + ".tmp", "w") as f:
            json.dump({"step": i + 1, "losses": losses}, f)
        os.replace(progress_path + ".tmp", progress_path)
    notify_complete(endpoints, trainer_id=0)


if __name__ == "__main__":
    main()
