"""The trainer's vocabulary-wide head — the output projection and the loss as
the ONE op ``fc_softmax_with_cross_entropy`` and its grad op through the Fluid
lowering, at the train cells' size (96 x 256 positions a chip, 37,000
classes) — checked with the TPU's own compiler, for a v5e that is described
and not attached (no chip, no chip time).

What a CPU run cannot show: which tensors of the vocabulary's width XLA:TPU
keeps in HBM, and which passes it makes over them.  The forward is the Mosaic
call ``proj_xent_fwd`` (``kernels/xent.py``), which writes the logits and each
row's log-sum-exp in one visit: ONE tensor of that width is written, NO
reduction over the classes stands outside the kernel (XLA's own pair came
back for the sum of the exponentials: 3.64 GB read for 24,576 sums), a gather
takes the labels' logits, and ``(softmax - onehot) * g`` — the softmax from
the forward's ``LSE`` — lives inside the two gradient products.

Two activations: float32, as ``models/transformer.build(dtype="bfloat16")``
hands them to the projection (float32 activations, bf16 weights: float32
logits, 3.64 GB), and bf16 (bf16 logits, 1.82 GB: nothing float32 of that
width at all).

The topology is described inside a fixture, never at import: one process at
a time may load the TPU's library, and every xdist worker imports every test
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import paddle_tpu as fluid
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import Executor, Scope, scope_guard
from paddle_tpu.core.lowering import analyze_block, build_block_fn
from paddle_tpu.core.program import Program, program_guard
from paddle_tpu.kernels import xent
from paddle_tpu.ops import nn_ops

L = fluid.layers
B, T, D, V = 96, 256, 512, 37000
# the logits, as the program sees them and as the kernel writes them: a
# sequence's positions along the lanes
WIDE = re.compile(rf"(\w+)\[(?:{B},{T},{V}|{B * T},{V}|{B},{V},{T})\]")
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")
FWD = "/fwd/out_proj/fc_softmax_with_cross_entropy/"
BWD = "/bwd/out_proj/fc_softmax_with_cross_entropy_grad/"
GB = 1e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _head():
    """The end of ``models/transformer.build``, under its scope names."""
    x = L.data("x", [T, D], dtype="bfloat16", stop_gradient=False)
    lbl_ids = L.data("lbl_ids", [T], dtype="int64")
    tgt_mask = L.data("tgt_mask", [T])
    with fluid.name_scope("out_proj"):
        loss = L.fc_softmax_with_cross_entropy(
            x, L.unsqueeze(lbl_ids, [2]), V, num_flatten_dims=2,
            param_attr=fluid.ParamAttr(name="tgt.out_proj"))
    with fluid.name_scope("loss"):
        masked = L.elementwise_mul(L.squeeze(loss, [2]), tgt_mask)
        avg_cost = L.elementwise_div(L.reduce_sum(masked),
                                     L.reduce_sum(tgt_mask))
    fluid.optimizer.Adam(1e-3, beta1=0.9, beta2=0.98,
                         epsilon=1e-9).minimize(avg_cost)
    return avg_cost


@pytest.fixture(scope="module")
def head():
    """The program, its plan and its state's shapes (started on the CPU)."""
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 24
    with program_guard(prog, startup), unique_name.guard():
        cost = _head()
    scope = Scope()
    with scope_guard(scope):
        Executor().run(startup)
        plan = analyze_block(prog, 0, ["lbl_ids", "tgt_mask", "x"],
                             [cost.name, "x@GRAD"])
        state = [[np.asarray(scope.find_var(n)) for n in names]
                 for names in (plan.donated_reads, plan.const_reads)]
    return prog, plan, state


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The process sees the CPU: the op's choice is made as on a TPU, and the
    kernel it takes is handed to Mosaic and not to the interpreter."""
    real = nn_ops._proj_xent_impl
    monkeypatch.setattr(nn_ops, "_proj_xent_impl",
                        lambda backend, *a, **kw: real("tpu", *a, **kw))
    monkeypatch.setattr(xent, "pallas_interpret", lambda: False)


def _compile(topo, head, activations, mesh):
    """One step of the head for the described chip, or for the four of them
    under a ``dp`` mesh: the batch sharded, the state replicated."""
    prog, plan, state = head
    if mesh:
        mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
        rows, whole = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())
    else:
        rows = whole = SingleDeviceSharding(topo.devices[0])
    batch = B * (4 if mesh else 1)
    feeds = [jax.ShapeDtypeStruct((batch, T), jnp.int32, sharding=rows),
             jax.ShapeDtypeStruct((batch, T), jnp.float32, sharding=rows),
             jax.ShapeDtypeStruct((batch, T, D), activations, sharding=rows)]
    donated, const = [[jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=whole)
                       for a in arrays] for arrays in state]
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=whole)
    # tier-1 turns x64 on; the chip's processes never do
    with jax.enable_x64(False):
        return jax.jit(build_block_fn(prog, plan, mesh=mesh or None)).lower(
            feeds, donated, const, key).compile()


def _in_hbm(text):
    """(name, opcode, op_name, dtypes written, dtypes read) of every
    instruction OUTSIDE a fusion's body that writes or reads a tensor of the
    vocabulary's width: what is inside a body never reaches HBM."""
    out = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \(.*\) -> .*\{\n)", text):
        header, _, body = comp.partition("\n")
        name = re.match(r"(?:ENTRY )?%([\w.\-]+)", header)
        if not name or name.group(1).startswith("fused_computation"):
            continue
        shapes, rows = {}, []
        for line in body.splitlines():
            m = INSTRUCTION.match(line)
            if m:
                shapes[m.group(1)] = m.group(2)
                rows.append(m.groups() + (line,))
        for name, shape, opcode, rest, line in rows:
            if opcode in ("get-tuple-element", "bitcast", "tuple"):
                continue
            operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
            read = [WIDE.search(shapes[o]).group(1) for o in operands
                    if o in shapes and WIDE.search(shapes[o])]
            wrote = WIDE.findall(shape)
            if wrote or read:
                op_name = re.search(r'op_name="([^"]*)"', line)
                out.append((name, opcode, op_name.group(1) if op_name else "",
                            wrote, read))
    return out


@pytest.mark.parametrize("mesh", [False, True], ids=["one_chip", "dp4"])
@pytest.mark.parametrize("activations, logits, temp_gb", [
    (jnp.float32, "f32", 4.0),      # the train cells': 3.64 (7.28 before)
    (jnp.bfloat16, "bf16", 2.5),    # 1.82 (5.46 before)
], ids=["f32_logits", "bf16_logits"])
def test_the_logits_are_the_only_vocabulary_wide_tensor_in_hbm(
        topo, head, as_on_a_tpu, activations, logits, temp_gb, mesh):
    compiled = _compile(topo, head, activations, mesh)
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "proj_xent_fwd" in text
    wide = _in_hbm(text)
    writers = [row for row in wide if row[3]]
    # ONE write, the kernel's, in the dtype the logits have: no float32 copy
    # of bf16 logits, no ``logits - lse``, no dLogits
    assert [(w[1], w[3], FWD in w[2]) for w in writers] == \
        [("custom-call", [logits], True)], writers
    readers = [row for row in wide if row[4]]
    assert all(r[4] == [logits] and r[1] == "fusion" for r in readers), readers
    products = [r for r in readers if BWD in r[2]]
    gathers = [r for r in readers if FWD in r[2] and "gather" in r[2]]
    # the two gradient products carry softmax - onehot as their producer;
    # besides them the gather, and NO pass over the classes: the kernel
    # handed the loss its log-sum-exp
    assert (len(products), len(gathers), len(readers)) == (2, 1, 3), readers
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gb * GB
    if mesh:
        # the kernel runs a shard's rows against the whole of ``w``: the
        # batch is what is sharded, and nothing is gathered
        for collective in ("all-gather", "all-to-all", "collective-permute"):
            assert collective not in text, collective
        for gathered in (f"[{4 * B},{T},{V}]", f"[{4 * B * T},{V}]",
                         f"[{4 * B},{V},{T}]"):
            assert gathered not in text, gathered


def test_the_fallback_is_the_pair_xla_made_of_the_two_ops(topo, head):
    """The control: with the op's choice left as this process makes it (no
    TPU: ``jnp.matmul`` + ``logsumexp``) the same program compiles to what the
    ``mul`` + ``softmax_with_cross_entropy`` pair did — the projection's
    fusion writes the logits with the row maximum inside, and ONE pass comes
    back over them to sum the exponentials.  That pass is what the kernel
    takes off the step; the backward is the same two products either way."""
    compiled = _compile(topo, head, jnp.float32, False)
    text = compiled.as_text()
    assert "proj_xent_fwd" not in text
    wide = _in_hbm(text)
    writers = [row for row in wide if row[3]]
    assert [(w[1], w[3], FWD + "dot_general" in w[2]) for w in writers] == \
        [("fusion", ["f32"], True)], writers
    readers = [row for row in wide if row[4]]
    products = [r for r in readers if BWD in r[2]]
    passes = [r for r in readers if FWD in r[2] and "gather" not in r[2]]
    assert (len(products), len(passes), len(readers)) == (2, 1, 4), readers
    assert "reduce_sum" in passes[0][2], passes
    assert compiled.memory_analysis().temp_size_in_bytes < 4.0 * GB
