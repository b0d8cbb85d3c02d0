"""Every lowered op is traced under ``<role>/<op_namescope...>/<op.type>[/<param>]``
(``core/lowering.py op_scope``), and the split into forward, backward and
optimizer is true on the compiled text: a grad op that re-traces its forward
names the copy as the original, so XLA's CSE cannot move a forward matmul
under ``bwd``."""
import collections
import re

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import compile_cache, lowering, unique_name
from paddle_tpu.core.executor import (Executor, Scope, _as_device_array,
                                      scope_guard)
from paddle_tpu.core.lowering import analyze_block, build_block_fn, op_scope
from paddle_tpu.core.program import Program, program_guard
from paddle_tpu.models import transformer
from paddle_tpu.ops import attention_ops

L = fluid.layers
ROLE = re.compile(r"(?:^|/)(fwd|bwd|opt)/")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+? ([\w\-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# what the compiler makes itself and names after nothing of the program
PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "copy", "broadcast", "reduce-window", "fusion", "iota",
            "copy-start", "copy-done"}
B, T, V = 4, 8, 64


def _build(build_fn):
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 5
    with program_guard(prog, startup), unique_name.guard():
        out = build_fn()
    return prog, startup, out


def _transformer(impl="base", n_layer=2):
    return _build(lambda: transformer.build(
        src_vocab=V, tgt_vocab=V, max_len=T, d_model=16, n_head=2, d_ffn=32,
        n_layer=n_layer, dropout=0.1, warmup_steps=10, attention_impl=impl))


def _feed():
    rng = np.random.RandomState(0)
    ids = lambda: rng.randint(0, V, (B, T)).astype("int64")  # noqa: E731
    ones = np.ones((B, T), "float32")
    return {"src_ids": ids(), "tgt_ids": ids(), "lbl_ids": ids(),
            "src_mask": ones, "tgt_mask": ones}


def _lowered(prog, startup, feed, fetch, steps=0):
    """The block as the executor lowers it: one step, or ``run_steps``' scan
    over ``steps`` of them."""
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        names = sorted(feed)
        plan = analyze_block(prog, 0, names, [fetch.name])
        block = prog.global_block
        vals = [_as_device_array(feed[n], block.var_or_none(n)) for n in names]
        if steps:
            fn = exe._make_scan_builder(prog, plan)()
            vals = [np.stack([np.asarray(v)] * steps) for v in vals]
        else:
            fn = build_block_fn(prog, plan)
        donated = [np.asarray(scope.find_var(n)) for n in plan.donated_reads]
        const = [np.asarray(scope.find_var(n)) for n in plan.const_reads]
        return jax.jit(fn).lower(vals, donated, const, jax.random.PRNGKey(0))


def _compiled_text(*args, **kw):
    """The optimised HLO of :func:`_lowered`'s block."""
    return _lowered(*args, **kw).compile().as_text()


def _instructions(text):
    """(opcode, op_name) of every instruction of the compiled text.  Where
    XLA folded two instructions into one it joins their names with ``;``:
    each is listed."""
    out = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            name = OP_NAME.search(line)
            for one in (name.group(1) if name else "").split(";"):
                out.append((m.group(1), one))
    return out


def _dots(text):
    return collections.Counter(
        name for opcode, name in _instructions(text) if opcode == "dot")


@pytest.fixture(scope="module", params=["base", "auto", "short"])
def lowered(request):
    """``base``: attention as ``matmul``/``softmax`` ops, every grad through
    ``vjp_grad``; ``auto``: ``fused_attention``, whose own grad rule
    re-traces its forward; ``short``: ``auto`` resolved as on a TPU, the
    forward and backward kernels of ``kernels/short_attention.py`` (in
    interpret mode here) under a ``custom_vjp``."""
    impl = request.param
    prog, startup, (_, loss, _) = _transformer(
        "auto" if impl == "short" else impl)
    with pytest.MonkeyPatch.context() as patch:
        if impl == "short":
            patch.setattr(attention_ops, "_auto_impl",
                          lambda *a, **kw: "short")
        text = _compiled_text(prog, startup, _feed(), loss)
    return impl, prog, text


def test_every_instruction_of_the_program_carries_exactly_one_role(lowered):
    _, _, text = lowered
    named = 0
    for opcode, name in _instructions(text):
        if name.startswith("jit("):
            named += 1
            assert len(ROLE.findall(name)) == 1, (opcode, name)
        elif not name:
            assert opcode in PLUMBING, opcode
    assert named > 500


def test_a_forward_matmul_is_never_filed_under_bwd_after_cse(lowered):
    """One dot a forward product, two a backward one (both operands are
    differentiated everywhere in this model): a forward copy that survived
    CSE under the grad op's name would make a third."""
    impl, prog, text = lowered
    dots = _dots(text)
    ops = collections.Counter(op_scope(op) for op in prog.global_block.ops)
    # the output projection's product is the fused op's: one dot forward,
    # the two gradient products under its grad op
    kinds = {"mul": 1, "fc_softmax_with_cross_entropy": 1}
    # a kernel's body (interpret mode) holds two products a head forward and
    # five backward, and the model has two heads.  Interpreted, a kernel is a
    # while loop, which XLA's CSE does not fold: the grad op's re-traced
    # forward stays, named as the forward op's (on the TPU the two custom
    # calls fold: tests/test_short_attention_v5e_compile.py)
    kinds.update({"base": {"matmul": 1}, "auto": {"fused_attention": 2},
                  "short": {"fused_attention": 8}}[impl])
    seen = 0
    for scope, n_ops in ops.items():
        role, *_, kind = scope.split("/")
        if role != "fwd" or kind not in kinds:
            continue
        seen += 1
        fwd = sum(n for name, n in dots.items() if f"/{scope}/" in name)
        grad = "bwd/" + scope[4:] + "_grad"
        bwd = sum(n for name, n in dots.items() if f"/{grad}/" in name)
        assert fwd == n_ops * kinds[kind], (scope, fwd)
        if impl == "short" and kind == "fused_attention":
            # one backward kernel, and nothing of the forward's under bwd
            assert bwd == n_ops * 10, (scope, bwd)
        else:
            assert bwd == 2 * fwd, (scope, bwd)
    assert seen >= 10
    assert all(ROLE.findall(name) in (["fwd"], ["bwd"]) for name in dots)
    # the first feed-forward product of enc_0, by name
    assert dots["jit(fn_s1)/fwd/enc_0/ffn/mul/dot_general"] == 2


def test_an_adam_update_is_filed_under_its_parameter(lowered):
    _, prog, text = lowered
    names = {name for _, name in _instructions(text)}
    for param in ("tgt.out_proj", "src.word_emb", "enc.0.ffn.fc1.w"):
        assert any(f"/opt/adam/{param}/" in n for n in names), param
    assert any("/opt/increment/" in n or "/opt/scale/" in n for n in names)
    scopes = {op_scope(op) for op in prog.global_block.ops}
    assert {"fwd/enc_1/self_attn/layer_norm", "bwd/dec_0/cross_attn/mul_grad",
            "fwd/out_proj/fc_softmax_with_cross_entropy",
            "fwd/src_embed/lookup_table", "fwd/loss/reduce_sum",
            "bwd/out_proj/fc_softmax_with_cross_entropy_grad",
            "opt/adam/tgt.word_emb"} <= scopes
    assert not {"fwd/loss/softmax_with_cross_entropy", "fwd/out_proj/mul",
                "bwd/out_proj/mul_grad"} & scopes


LOSS, LOSS_GRAD = ("fwd/out_proj/fc_softmax_with_cross_entropy",
                   "bwd/out_proj/fc_softmax_with_cross_entropy_grad")


def test_the_loss_grad_reads_the_forwards_lse_and_retraces_none():
    """``fc_softmax_with_cross_entropy_grad`` is a closed form with a rule of
    its own that READS the forward's ``LSE``: BEFORE any optimisation the
    log-sum-exp stands once, under the forward op's name, the cotangent half
    under the grad op's, and nothing of the loss under any other."""
    prog, startup, (_, loss, _) = _transformer(n_layer=1)
    names = collections.Counter(re.findall(
        r'loc\("jit\(fn_s1\)/([^"]*softmax_with_cross_entropy[^"]*)"',
        _lowered(prog, startup, _feed(), loss).as_text(debug_info=True)))
    assert {name.rsplit("/", 1)[0].split("/jit(")[0] for name in names} == \
        {LOSS, LOSS_GRAD}
    for primitive in ("reduce_max", "reduce_sum", "log"):
        assert names[f"{LOSS}/{primitive}"] == 1, primitive
        assert f"{LOSS_GRAD}/{primitive}" not in names
    assert (names[f"{LOSS}/dot_general"],
            names[f"{LOSS_GRAD}/dot_general"]) == (1, 2)
    for primitive in ("iota", "eq", "exp", "mul"):      # softmax - onehot
        assert names[f"{LOSS_GRAD}/{primitive}"] == 1, primitive
    assert f"{LOSS}/iota" not in names


def test_the_lse_is_the_forward_ops_in_the_compiled_text(lowered):
    _, _, text = lowered
    names = [n for _, n in _instructions(text)
             if "softmax_with_cross_entropy" in n]
    assert {"fwd" if f"/{LOSS}/" in n else
            "bwd" if f"/{LOSS_GRAD}/" in n else n for n in names} == \
        {"fwd", "bwd"}
    assert any(n.endswith(f"/{LOSS}/log") for n in names)
    assert not any(n.endswith(("/log", "/reduce_max")) for n in names
                   if f"/{LOSS_GRAD}/" in n)


def test_the_models_scope_names_do_not_depend_on_what_was_built_before():
    first = {op_scope(op) for op in _transformer()[0].global_block.ops}
    again = {op_scope(op) for op in _transformer()[0].global_block.ops}
    assert first == again and "fwd/enc_0/ffn/mul" in first
    assert not any(re.search(r"(embed|enc_\d|dec_\d|loss|out_proj)_\d", s)
                   for s in again)


def _two_scopes():
    x = L.data("x", [4])
    with fluid.name_scope("a"):
        with fluid.name_scope("b"):
            h = L.fc(x, 8)
    return L.mean(h)


def test_name_scopes_nest_and_a_program_without_them_reads_fwd_op_type():
    prog, startup, loss = _build(_two_scopes)
    feed = {"x": np.ones((2, 4), "float32")}
    names = {n for _, n in _instructions(
        _compiled_text(prog, startup, feed, loss))}
    assert any("/fwd/a/b/mul/" in n for n in names)

    def plain():
        return L.mean(L.fc(L.data("x", [4]), 8))

    prog, startup, loss = _build(plain)
    assert {op_scope(op) for op in prog.global_block.ops} == {
        "fwd/mul", "fwd/elementwise_add", "fwd/mean"}
    names = {n for _, n in _instructions(
        _compiled_text(prog, startup, feed, loss))}
    assert any(n.endswith("/fwd/mul/dot_general") for n in names)


def test_two_lowerings_of_one_program_name_their_instructions_alike():
    prog, startup, (_, loss, _) = _transformer(n_layer=1)
    feed = _feed()
    one = {n for _, n in _instructions(
        _compiled_text(prog, startup, feed, loss))}
    two = {n for _, n in _instructions(
        _compiled_text(prog, startup, feed, loss))}
    assert one == two and len(one) > 200


def test_the_scan_of_run_steps_keeps_the_scopes():
    prog, startup, (_, loss, _) = _transformer(n_layer=1)
    text = _compiled_text(prog, startup, _feed(), loss, steps=3)
    names = [n for _, n in _instructions(text) if n.startswith("jit(")]
    body = [n for n in names if "/while/body/" in n]
    assert len(body) > 200
    for n in body:
        # the scan's own slicing of its feeds and stacking of its fetches
        # is JAX's, named ``while/body/<primitive>``: no role reaches it
        assert len(ROLE.findall(n)) == 1 or \
            re.fullmatch(r"jit\(multi_s1\)/while/body/\w+", n), n
    for want in ("fwd/enc_0/ffn/mul", "opt/adam/tgt.out_proj"):
        assert any(re.search(rf"/while/body/(\w+/)?{want}/", n) for n in body)
    assert "jit_multi_s1" in text.splitlines()[0]


def _rnn():
    x = L.data("x", [8, 4], append_batch_size=True)
    rnn = fluid.layers.StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)
        h_prev = rnn.memory(shape=[16], batch_ref=x_t, init_value=0.0)
        with fluid.name_scope("cell"):
            h = L.fc([x_t, h_prev], 16, act="tanh")
        rnn.update_memory(h_prev, h)
        rnn.step_output(h)
    loss = L.mean(L.square(L.reduce_mean(rnn(), dim=1)))
    fluid.optimizer.SGD(0.05).minimize(loss)
    return loss


def _while():
    x = L.data("x", [4])
    i = L.fill_constant((), "float32", 0.0)
    n = L.fill_constant((), "float32", 3.0)
    s = L.fill_constant((), "float32", 0.0)
    cond = L.control_flow.less_than(i, n)
    w = L.While(cond)
    with w.block():
        with fluid.name_scope("acc"):
            L.assign(L.elementwise_add(s, L.reduce_sum(x)), s)
        L.increment(i, 1.0)
        L.control_flow.less_than(i, n, cond=cond)
    return s


@pytest.mark.parametrize("build_fn, feed, want", [
    (_rnn, {"x": np.ones((2, 8, 4), "float32")},
     [r"/fwd/static_rnn/while/body/(\w+/)?cell/mul/",
      r"/bwd/static_rnn_grad/transpose\(jvp\(\)\)/while/body/(\w+/)?cell/mul/"]),
    (_while, {"x": np.ones((2, 4), "float32")},
     ["/fwd/while/while/body/acc/reduce_sum/"]),
])
def test_a_sub_block_keeps_the_scopes_under_its_control_flow_op(
        build_fn, feed, want):
    """A sub-block's ops inherit their control-flow op's role, so a path
    still holds one role, and the model's scope names still match."""
    prog, startup, fetch = _build(build_fn)
    names = [n for _, n in _instructions(
        _compiled_text(prog, startup, feed, fetch)) if n.startswith("jit(")]
    for pattern in want:
        assert any(re.search(pattern, n) for n in names), pattern
    for n in names:
        assert len(ROLE.findall(n)) == 1, n


def test_clipping_and_regularisation_are_the_optimizers():
    def build():
        loss = L.mean(L.fc(L.data("x", [4]), 8, param_attr=fluid.ParamAttr(
            name="w", regularizer=fluid.regularizer.L2Decay(1e-3))))
        fluid.clip.set_gradient_clip(fluid.clip.GradientClipByGlobalNorm(1.0))
        try:
            fluid.optimizer.SGD(0.1).minimize(loss)
        finally:
            fluid.clip.set_gradient_clip(None)
        return loss

    prog, _, _ = _build(build)
    scopes = [op_scope(op) for op in prog.global_block.ops]
    for kind in ("__global_norm_sq__", "__global_norm_factor__"):
        assert f"opt/{kind}" in scopes
    rewrites = [s for s in scopes if s in ("opt/scale", "opt/sum",
                                           "opt/elementwise_mul")]
    assert len(rewrites) >= 4
    assert "opt/sgd/w" in scopes and "bwd/mul_grad" in scopes
    # by role they stay Backward: the distribute transpiler keeps them on
    # the trainer
    from paddle_tpu.core.program import OP_ROLE_ATTR, OpRole
    assert all(op.attr(OP_ROLE_ATTR) == OpRole.Backward
               for op in prog.global_block.ops
               if op.type.startswith("__global_norm"))


def test_a_program_compiled_before_its_scopes_is_not_handed_back(monkeypatch):
    """Tier A keys on the grammar's version through the environment digest,
    tier B through the compiled program's name."""
    prog, _, loss = _build(_two_scopes)
    sig = (("x", (2, 4), "float32"),)

    def key():
        monkeypatch.setattr(compile_cache, "_env_digest_cache", None)
        return compile_cache.fingerprint(prog, sig, [loss.name], True, "run")

    now = key()
    assert key() == now
    monkeypatch.setattr(compile_cache, "SCOPE_GRAMMAR",
                        compile_cache.SCOPE_GRAMMAR + 1)
    assert key() != now
    assert compile_cache.program_name("fn") == \
        f"fn_s{compile_cache.SCOPE_GRAMMAR}"
    monkeypatch.undo()
    plan = analyze_block(prog, 0, ["x"], [loss.name])
    assert build_block_fn(prog, plan).__name__ == "fn_s1"
    assert lowering.op_scope(prog.global_block.ops[0]) == "fwd/a/b/mul"
