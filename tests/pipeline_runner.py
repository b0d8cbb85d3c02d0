"""Subprocess entry for the 2-process RPC pipeline smoke test: each
process runs ONE pipeline stage over the striped RPC transport
(paddle_tpu/pipeline/rpc.py), driven by PIPE_* env vars.  The last
stage appends its per-minibatch loss to PIPE_OUT as JSON lines."""
import json
import os
import sys

import numpy as np


def build_model():
    """Tiny deterministic MLP classifier (both processes must derive the
    IDENTICAL program: fixed seeds, fresh name scope)."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.program import Program, program_guard

    prog, startup = Program(), Program()
    prog.random_seed = 13
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [16])
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu")
        h = fluid.layers.fc(h, 32, act="relu")
        logits = fluid.layers.fc(h, 4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(logits, y))
        fluid.optimizer.Adam(learning_rate=5e-3).minimize(loss)
    return prog, startup, loss


def batches(steps, batch=16, seed=21):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(steps):
        x = rng.randn(batch, 16).astype("float32")
        y = (x.sum(axis=1, keepdims=True) > 0).astype("int64") + \
            2 * (x[:, :1] > 0).astype("int64")
        out.append({"x": x, "y": y})
    return out


def transpile(prog, startup, loss):
    import paddle_tpu.pipeline as pipe
    t = pipe.PipelineTranspiler()
    return t.transpile(prog, startup, num_stages=2, num_microbatches=4,
                       loss_name=loss.name)


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from paddle_tpu.pipeline.rpc import PipelineStageWorker

    stage = int(os.environ["PIPE_STAGE"])
    endpoints = os.environ["PIPE_ENDPOINTS"].split(",")
    steps = int(os.environ.get("PIPE_STEPS", "3"))
    schedule = os.environ.get("PIPE_SCHEDULE", "1f1b")
    out_path = os.environ.get("PIPE_OUT")

    prog, startup, loss = build_model()
    pp = transpile(prog, startup, loss)
    worker = PipelineStageWorker(pp, stage, endpoints, schedule=schedule)
    worker.init()
    for i, feed in enumerate(batches(steps)):
        l = worker.run_minibatch(feed)
        if stage == pp.num_stages - 1 and out_path:
            with open(out_path, "a") as f:
                f.write(json.dumps({"step": i, "loss": l}) + "\n")
                f.flush()
    worker.shutdown()
    print(f"pipeline stage {stage} done", flush=True)


if __name__ == "__main__":
    main()
