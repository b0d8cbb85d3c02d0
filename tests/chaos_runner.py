"""Subprocess entry for the chaos suite (tests/test_chaos.py): HA
pserver/backup/trainer/master roles driven by PADDLE_*/CHAOS_* env vars.

Faults are armed per process via ``FLAGS_fault_inject`` in the child's
environment (the flags registry bootstraps from env at import — no code
path differs from production).  Every role appends its flight-recorder
event ring to ``CHAOS_EVENTS`` on the way out, so the test can assert
the cross-process note chain (death → promotion → re-resolution) that
the acceptance bar demands.

Roles (PADDLE_TRAINING_ROLE):
- ``PSERVER``  primary for PADDLE_CURRENT_ENDPOINT; CHAOS_BACKUP names
  its backup replica's physical endpoint (arms HA replication).
- ``BACKUP``   backup replica for PADDLE_CURRENT_ENDPOINT, bound at
  CHAOS_BACKUP; registers as a registry standby and promotes on the
  primary's lease expiry.
- ``TRAINER``  sync-mode trainer; writes per-step losses to
  CHAOS_PROGRESS (atomic json) and exits cleanly at DIST_STEPS.
- ``MASTER``   one HA master candidate (CHAOS_CANDIDATE id); serves
  until killed or told to stop via CHAOS_STOP_FILE.
"""
import json
import os
import sys
import time

import numpy as np


def _dump_events(tag):
    """Write this process's flight ring next to CHAOS_EVENTS (one file
    per process — the test stitches the cross-process story)."""
    path = os.environ.get("CHAOS_EVENTS")
    if not path:
        return
    from paddle_tpu.observability import flight
    flight.export_events(f"{path}.{os.getpid()}", role=tag)


def _build_transpiler():
    import paddle_tpu as fluid
    from paddle_tpu.distributed.transpiler import DistributeTranspilerConfig
    from dist_model import build

    endpoints = os.environ["PADDLE_PSERVER_ENDPOINTS"].split(",")
    prog, startup, loss = build(
        lr=0.05, optimizer=os.environ.get("CHAOS_OPTIMIZER", "sgd"))
    cfg = DistributeTranspilerConfig()
    cfg.backup_endpoints = os.environ.get("CHAOS_BACKUPS", "")
    cfg.lease_ttl = float(os.environ.get("CHAOS_LEASE_TTL", "0") or 0)
    cfg.checkpoint_dir = os.environ.get("CHAOS_CKPT_DIR") or None
    cfg.checkpoint_sharded = os.environ.get("CHAOS_CKPT_SHARDED") == "1"
    cfg.min_block_size = int(os.environ.get("CHAOS_MIN_BLOCK",
                                            "8192") or 8192)
    if cfg.checkpoint_dir:
        cfg.checkpoint_every_rounds = int(
            os.environ.get("CHAOS_CKPT_EVERY", "1"))
    t = fluid.DistributeTranspiler(config=cfg)
    t.transpile(trainer_id=0, program=prog, pservers=",".join(endpoints),
                trainers=1, sync_mode=True, startup_program=startup)
    return t, startup, loss


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    role = os.environ["PADDLE_TRAINING_ROLE"]

    if role == "MASTER":
        from paddle_tpu.distributed.master import serve_master_ha
        ha = serve_master_ha(
            os.environ["PADDLE_CURRENT_ENDPOINT"],
            os.environ["FLAGS_pserver_registry"],
            int(os.environ["CHAOS_CANDIDATE"]),
            lease_ttl=float(os.environ.get("CHAOS_LEASE_TTL", "1.0")),
            lease_timeout=float(os.environ.get("CHAOS_LEASE_TIMEOUT",
                                               "3.0")))
        stop_file = os.environ.get("CHAOS_STOP_FILE")
        try:
            while not (stop_file and os.path.exists(stop_file)):
                time.sleep(0.1)
        finally:
            _dump_events(f"master-{os.environ['CHAOS_CANDIDATE']}")
            ha.stop()
        return

    from paddle_tpu.core.executor import Executor, Scope
    from paddle_tpu.distributed import notify_complete

    t, startup, loss = _build_transpiler()
    endpoints = os.environ["PADDLE_PSERVER_ENDPOINTS"].split(",")
    scope = Scope()
    exe = Executor()

    if role in ("PSERVER", "BACKUP"):
        ep = os.environ["PADDLE_CURRENT_ENDPOINT"]
        # bit-identical named draws: primary and backup start from the
        # SAME parameter state (replication keeps them in lockstep after)
        exe.run(t.get_startup_program(ep), scope=scope)
        ps_prog = (t.get_backup_program(ep) if role == "BACKUP"
                   else t.get_pserver_program(ep))
        # supervised fleets: PADDLE_BIND_ENDPOINT (e.g. "127.0.0.1:0")
        # binds an EPHEMERAL port while keeping the logical identity —
        # the heartbeat announces logical -> real port through the
        # registry, so replacements never race for a released port
        bind = os.environ.get("PADDLE_BIND_ENDPOINT")
        if bind:
            for op in ps_prog.global_block.ops:
                if op.type == "listen_and_serv":
                    op.attrs["bind_endpoint"] = bind
        try:
            exe.run(ps_prog, scope=scope)
        finally:
            _dump_events(role.lower())
        return

    # TRAINER
    tp = t.get_trainer_program()
    # elastic-resume phase window: steps [start, start + n_steps) of a
    # DIST_TOTAL_STEPS-long deterministic batch stream (a resized
    # trainer resumes from the checkpoint's cut over the same data).
    # DIST_STEPS unset with DIST_TOTAL_STEPS set = "run to the end"
    # (the supervisor's restart path only knows the resume step)
    start = int(os.environ.get("DIST_START_STEP", "0"))
    steps_env = os.environ.get("DIST_STEPS")
    if steps_env:
        n_steps = int(steps_env)
    elif os.environ.get("DIST_TOTAL_STEPS"):
        n_steps = int(os.environ["DIST_TOTAL_STEPS"]) - start
    else:
        n_steps = 20
    if start > 0:
        # resuming mid-run: pull the LIVE (checkpoint-restored) params
        # from the pservers instead of fresh local init — the joining-
        # trainer hydration path of get_trainer_startup_program
        exe.run(t.get_trainer_startup_program(), scope=scope)
    else:
        exe.run(startup, scope=scope)
    from dist_model import batches
    total = int(os.environ.get("DIST_TOTAL_STEPS", str(start + n_steps)))
    # CHAOS_NOTIFY_AT: "6:wait,12" = checkpoint_notify at global steps
    # 6 and 12, blocking on the two-phase commit for entries tagged
    # ":wait" (the fleet-cut trigger of the resize story)
    notify_spec = {}
    for ent in filter(None,
                      os.environ.get("CHAOS_NOTIFY_AT", "").split(",")):
        step_s, _, tag = ent.partition(":")
        notify_spec[int(step_s)] = tag == "wait"
    progress_path = os.environ["CHAOS_PROGRESS"]
    losses = []
    try:
        for i, (x, y) in enumerate(batches(total)[start:start + n_steps],
                                   start=start + 1):
            (l,) = exe.run(tp, feed={"x": x, "y": y}, fetch_list=[loss],
                           scope=scope)
            losses.append(float(np.asarray(l)))
            with open(progress_path + ".tmp", "w") as f:
                json.dump({"step": i - start, "global_step": i,
                           "losses": losses}, f)
            os.replace(progress_path + ".tmp", progress_path)
            if i in notify_spec:
                from paddle_tpu.distributed import notify_checkpoint
                notify_checkpoint(endpoints,
                                  os.environ["CHAOS_CKPT_DIR"], step=i)
                if notify_spec[i]:
                    import paddle_tpu.checkpoint as pckpt
                    assert pckpt.wait_step_complete(
                        os.environ["CHAOS_CKPT_DIR"], i, timeout=120), \
                        f"checkpoint step {i} never committed"
        notify_complete(endpoints, trainer_id=0)
    finally:
        _dump_events("trainer")


if __name__ == "__main__":
    main()
