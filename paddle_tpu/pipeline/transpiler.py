"""Pipeline transpiler: one trained Program → K per-stage programs.

The missing axis of the parallelism matrix (ROADMAP item 2; survey §2.7
names PP among the modern axes to design TPU-first, with the reference's
layer-placement precedent in ``legacy/gserver/.../ParallelNeuralNetwork.h``).
Takes the full program (forward + ``append_backward`` + optimizer ops)
and splits it into K **stages**, each a trio of standalone programs:

- ``fwd_program`` — the stage's forward ops; feeds are the global data
  feeds it consumes plus activations received from earlier stages;
  fetches are the boundary activations later stages consume plus the
  **stash** (forward values its own backward needs — the GPipe
  activation stash, visible as real per-microbatch bytes);
- ``bwd_program`` — the stage's backward ops plus appended
  **gradient-accumulation** ops: each optimizer-consumed gradient is
  scaled by ``1/M`` and added into a persistable ``<grad>@ACC`` var, so
  M microbatches accumulate exactly the full-batch mean gradient;
  fetches are the boundary activation-gradients sent upstream;
- ``opt_program`` — the (replicated) LR-schedule chain plus the stage's
  optimizer ops with their ``Grad`` input renamed to the accumulator,
  followed by accumulator zeroing — run ONCE per minibatch, after all
  M microbatches (gradient accumulation across microbatches before the
  optimizer block runs once).

Stage assignment: user-marked via ``program.pipeline_stage_guard`` /
explicit ``cut_points``, or cost-balanced automatically (contiguous
split of the forward ops on an analytic per-op flops estimate;
``balance="xla"`` refines the split once using real per-stage flops
from the PR-7 XLA cost attribution, ``observability/perf.cost_dict``).
Backward ops inherit the stage of their forward op (via the
``__fwd_out_slots__`` annotation ``core/backward.py`` stamps); gradient
``sum``/``assign`` combiners land on the stage that produced the summed
var; optimizer ops land on their parameter's stage.

Equal-weight caveat: microbatch-mean accumulation reproduces the
full-batch gradient exactly only when the loss is an equal-weight mean
over samples and every microbatch has the same weight (e.g. identical
token counts for a token-normalized loss) — the standard GPipe
contract.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..core.program import (EMPTY_VAR, OP_ROLE_ATTR, Operator, OpRole,
                            Program, Variable, default_main_program,
                            default_startup_program)

__all__ = ["PipelineTranspiler", "PipelineProgram", "StagePrograms",
           "balanced_cut_points", "op_flops_estimate", "xla_stage_flops",
           "split_microbatches", "PIPELINE_STAGE_ATTR", "ACC_SUFFIX"]

PIPELINE_STAGE_ATTR = "pipeline_stage"
ACC_SUFFIX = "@ACC"


def _role(op) -> int:
    return int(op.attr(OP_ROLE_ATTR, OpRole.Forward))


def _is_optimize_op(op) -> bool:
    return ("Param" in op.inputs and "Grad" in op.inputs
            and _role(op) == OpRole.Optimize)


def _real(names) -> List[str]:
    return [n for n in names if n and n != EMPTY_VAR]


def split_microbatches(feed: Dict[str, object], num_microbatches: int):
    """THE microbatch split contract, shared by every driver (in-process
    runner and RPC stage workers): each feed's leading (batch) dim must
    divide M; microbatch m gets rows ``[m*mb, (m+1)*mb)``.  Returns
    ``(stacked, per_mb)`` — ``stacked[n]`` is ``[M, mb, ...]`` (the
    run_steps scan layout), ``per_mb[m][n]`` the per-microbatch slice.
    """
    import numpy as np
    M = int(num_microbatches)
    stacked: Dict[str, object] = {}
    per_mb: List[Dict[str, object]] = [dict() for _ in range(M)]
    for n, v in feed.items():
        a = np.asarray(v)
        if a.ndim < 1 or a.shape[0] % M != 0:
            raise ValueError(
                f"feed {n!r} batch {a.shape[:1]} does not divide "
                f"num_microbatches={M}")
        mb = a.shape[0] // M
        s = a.reshape((M, mb) + a.shape[1:])
        stacked[n] = s
        for m in range(M):
            per_mb[m][n] = s[m]
    return stacked, per_mb


def op_flops_estimate(block, op, batch: int = 8) -> float:
    """Analytic per-op cost for stage balancing.  Dense contractions get
    a real flops formula; everything else counts output elements (a
    bandwidth proxy).  ``-1`` (batch) dims substitute ``batch``."""

    def shape(name):
        v = block.var_or_none(name)
        if v is None or v.shape is None:
            return None
        return tuple(batch if d == -1 else int(d) for d in v.shape)

    def numel(name):
        s = shape(name)
        if not s:
            return 0.0
        n = 1.0
        for d in s:
            n *= d
        return n

    out_elems = sum(numel(n) for n in _real(op.output_arg_names()))
    if op.type in ("matmul", "mul"):
        xs = shape(op.input("X")[0]) if op.input("X") else None
        if xs:
            k = xs[-2] if op.attr("transpose_x", False) else xs[-1]
            return 2.0 * out_elems * max(k, 1)
    if op.type in ("conv2d", "depthwise_conv2d"):
        ws = shape(op.input("Filter")[0]) if op.input("Filter") else None
        if ws and len(ws) == 4:
            co, ci, kh, kw = ws
            groups = max(int(op.attr("groups", 1) or 1), 1)
            return 2.0 * out_elems * ci * kh * kw / groups
    if op.type == "fc_softmax_with_cross_entropy":
        xs = shape(op.input("X")[0]) if op.input("X") else None
        if xs:      # the projection's product; the loss is its epilogue
            return 2.0 * numel(op.output("Logits")[0]) * max(xs[-1], 1)
    if op.type == "fused_attention":
        qs = shape(op.input("Q")[0]) if op.input("Q") else None
        if qs and len(qs) >= 2:
            # QK^T + PV: 2 matmuls of [T, dk] x [dk, T] shape class
            return 4.0 * numel(op.input("Q")[0]) * qs[-2]
    return max(out_elems, 1.0)


def balanced_cut_points(costs: Sequence[float], num_stages: int
                        ) -> List[int]:
    """Contiguous split of ``costs`` into ``num_stages`` parts with
    near-equal sums: cut after the prefix crosses each k/K share.
    Returns K-1 cut indices (first op index of each later stage)."""
    K = num_stages
    n = len(costs)
    if K > n:
        raise ValueError(f"cannot split {n} forward ops into {K} stages")
    total = float(sum(costs)) or 1.0
    cuts: List[int] = []
    acc, k = 0.0, 1
    for i, c in enumerate(costs):
        if k >= K:
            break
        target = total * k / K
        # crossing the k/K share: cut BEFORE this op when that lands
        # closer to the target (a single huge op must start a stage,
        # not silently absorb into the previous one), and always leave
        # at least one op per remaining stage
        if acc + c >= target and i >= k - 1:
            cut_at = i if (target - acc <= acc + c - target and i > 0
                           and (not cuts or i > cuts[-1])) else i + 1
            cut_at = min(cut_at, n - (K - k))
            if not cuts or cut_at > cuts[-1]:
                cuts.append(cut_at)
                k += 1
        elif i + 1 == n - (K - k):
            cuts.append(i + 1)
            k += 1
        acc += c
    while k < K:  # degenerate tails: force remaining cuts
        cut_at = n - (K - k)
        cuts.append(cut_at)
        k += 1
    return cuts


class StagePrograms:
    """One pipeline stage's emitted programs + boundary contract."""

    def __init__(self, idx: int):
        self.idx = idx
        self.fwd_program: Optional[Program] = None
        self.bwd_program: Optional[Program] = None
        self.opt_program: Optional[Program] = None
        self.startup_program: Optional[Program] = None
        self.fwd_feeds: List[str] = []      # global data feeds (forward)
        self.bwd_feeds: List[str] = []      # global data feeds (backward)
        self.recv_acts: Dict[str, int] = {}       # name -> src stage
        self.recv_acts_fwd: List[str] = []        # consumed by fwd ops
        self.recv_acts_bwd: List[str] = []        # consumed by bwd ops
        self.send_acts: Dict[str, List[int]] = {}  # name -> dst stages
        self.stash: List[str] = []                # fwd -> own bwd
        self.recv_grads: Dict[str, int] = {}      # name -> src stage
        self.send_grads: Dict[str, List[int]] = {}  # name -> dst stages
        self.fwd_fetches: List[str] = []
        self.bwd_fetches: List[str] = []
        self.param_accs: List[Tuple[str, str, str]] = []  # (param, grad, acc)
        self.loss_name: Optional[str] = None
        self.op_indices: Dict[str, List[int]] = {"F": [], "B": [], "O": []}

    @property
    def has_optimizer(self) -> bool:
        return self.opt_program is not None

    def activation_bytes(self, microbatch: int) -> int:
        """Per-microbatch bytes this stage must hold/ship forward: the
        boundary activations it sends plus its own stash."""
        import numpy as np
        from ..core.types import np_dtype
        total = 0
        blk = self.fwd_program.global_block if self.fwd_program else None
        if blk is None:
            return 0
        for n in set(self.fwd_fetches):
            v = blk.var_or_none(n)
            if v is None or v.shape is None:
                continue
            numel = 1
            for d in v.shape:
                numel *= microbatch if d == -1 else int(d)
            total += numel * np.dtype(np_dtype(v.dtype or "float32")).itemsize
        return total


class PipelineProgram:
    """The transpiled pipeline: K StagePrograms + the microbatch/schedule
    contract (built by :class:`PipelineTranspiler`, driven by
    ``pipeline/runner.py``)."""

    def __init__(self, stages: List[StagePrograms], num_microbatches: int,
                 loss_name: Optional[str], assignment: List[Optional[int]],
                 lr_chain: List[int]):
        self.stages = stages
        self.num_stages = len(stages)
        self.num_microbatches = num_microbatches
        self.loss_name = loss_name
        # per original-op stage (None = LR-chain op, replicated into
        # every optimizing stage's opt_program)
        self.op_stage_assignment = assignment
        self.lr_chain_ops = lr_chain

    def validate(self) -> None:
        """Structural invariants: every original op assigned exactly
        once (or LR-chain-replicated), every boundary recv matched by
        the producing stage's send."""
        for i, s in enumerate(self.op_stage_assignment):
            if s is None and i not in self.lr_chain_ops:
                raise AssertionError(f"op {i} is unassigned")
        for st in self.stages:
            for n, src in st.recv_acts.items():
                if st.idx not in self.stages[src].send_acts.get(n, []):
                    raise AssertionError(
                        f"stage {st.idx} receives activation {n!r} from "
                        f"{src}, which does not send it")
            for n, src in st.recv_grads.items():
                if st.idx not in self.stages[src].send_grads.get(n, []):
                    raise AssertionError(
                        f"stage {st.idx} receives grad {n!r} from {src}, "
                        f"which does not send it")
            for n, dsts in st.send_acts.items():
                for d in dsts:
                    if st.idx != self.stages[d].recv_acts.get(n):
                        raise AssertionError(
                            f"stage {st.idx} sends {n!r} to {d}, which "
                            f"does not expect it")

    def adjacent_only(self) -> bool:
        """True when every boundary crosses exactly one stage hop (the
        collective-permute transport's requirement)."""
        for st in self.stages:
            for n, src in st.recv_acts.items():
                if st.idx - src != 1:
                    return False
            for n, src in st.recv_grads.items():
                if src - st.idx != 1:
                    return False
        return True


class PipelineTranspiler:
    """Split a trained program into pipeline stages (see module doc)."""

    def transpile(self, program: Optional[Program] = None,
                  startup_program: Optional[Program] = None,
                  num_stages: Optional[int] = None,
                  num_microbatches: int = 4,
                  loss_name: Optional[str] = None,
                  cut_points: Optional[Sequence[int]] = None,
                  balance: str = "analytic",
                  batch_hint: int = 8) -> PipelineProgram:
        program = program or default_main_program()
        startup_program = startup_program or default_startup_program()
        if num_microbatches < 1:
            raise ValueError("num_microbatches must be >= 1")
        self.program = program
        self.startup_program = startup_program
        self.block = program.global_block
        self.ops = list(self.block.ops)
        self.loss_name = loss_name
        self.M = int(num_microbatches)

        self._classify_ops()
        fwd_assign = self._assign_forward(num_stages, cut_points,
                                          balance, batch_hint)
        self.K = max(fwd_assign.values()) + 1
        assignment = self._assign_all(fwd_assign)
        stages = self._emit(assignment)
        pp = PipelineProgram(stages, self.M, loss_name,
                             [assignment.get(i) for i in
                              range(len(self.ops))],
                             sorted(self.lr_chain))
        pp.validate()
        if balance == "xla" and cut_points is None and \
                not self._explicit_stages():
            pp = self._xla_rebalance(pp, num_stages, batch_hint)
        return pp

    # -- classification ----------------------------------------------------
    def _classify_ops(self) -> None:
        self.fwd_idx = [i for i, op in enumerate(self.ops)
                        if _role(op) == OpRole.Forward]
        self.opt_idx = [i for i, op in enumerate(self.ops)
                        if _is_optimize_op(op)]
        # the LR closure: every var feeding an optimizer op's
        # LearningRate slot, and the LRSched/Optimize-role ops that
        # (transitively) produce them — replicated per optimizing stage
        lr_names: Set[str] = set()
        for i in self.opt_idx:
            lr_names |= set(_real(self.ops[i].input("LearningRate")))
        needed = set(lr_names)
        chain: Set[int] = set()
        for i in range(len(self.ops) - 1, -1, -1):
            op = self.ops[i]
            r = _role(op)
            if i in self.opt_idx or r not in (OpRole.Optimize,
                                              OpRole.LRSched):
                continue
            if r == OpRole.LRSched or \
                    set(_real(op.output_arg_names())) & needed:
                chain.add(i)
                needed |= set(_real(op.input_arg_names()))
        self.lr_chain = chain
        self.lr_names = lr_names

    def _phase(self, i: int) -> str:
        if i in self.lr_chain or i in self.opt_idx:
            return "O"
        return "F" if _role(self.ops[i]) == OpRole.Forward else "B"

    def _explicit_stages(self) -> bool:
        return any(self.ops[i].has_attr(PIPELINE_STAGE_ATTR)
                   for i in self.fwd_idx)

    # -- forward assignment ------------------------------------------------
    def _assign_forward(self, num_stages, cut_points, balance,
                        batch_hint) -> Dict[int, int]:
        if self._explicit_stages():
            assign, cur = {}, 0
            for i in self.fwd_idx:
                if self.ops[i].has_attr(PIPELINE_STAGE_ATTR):
                    cur = int(self.ops[i].attr(PIPELINE_STAGE_ATTR))
                assign[i] = cur
            if num_stages is not None and \
                    max(assign.values()) + 1 != num_stages:
                raise ValueError(
                    f"pipeline_stage markers name "
                    f"{max(assign.values()) + 1} stages, num_stages="
                    f"{num_stages}")
        else:
            if num_stages is None or num_stages < 1:
                raise ValueError("num_stages required without "
                                 "pipeline_stage markers or cut_points")
            if cut_points is None:
                costs = self._op_costs(batch_hint)
                cut_points = balanced_cut_points(costs, num_stages)
            if len(cut_points) != num_stages - 1:
                raise ValueError(
                    f"{num_stages} stages need {num_stages - 1} cut "
                    f"points, got {len(cut_points)}")
            assign = {}
            for pos, i in enumerate(self.fwd_idx):
                s = 0
                for c in cut_points:
                    if pos >= c:
                        s += 1
                assign[i] = s
        self._validate_forward(assign)
        return assign

    def _op_costs(self, batch_hint: int,
                  scale: Optional[Dict[int, float]] = None) -> List[float]:
        """Per-forward-op costs (``scale``: per-stage correction factors
        from the XLA rebalance pass, keyed by a prior assignment)."""
        costs = []
        for i in self.fwd_idx:
            c = op_flops_estimate(self.block, self.ops[i], batch_hint)
            if scale:
                c *= scale.get(i, 1.0)
            costs.append(c)
        return costs

    def _validate_forward(self, assign: Dict[int, int]) -> None:
        prod: Dict[str, int] = {}
        for i in self.fwd_idx:
            s = assign[i]
            for n in _real(self.ops[i].input_arg_names()):
                if n in prod and prod[n] > s:
                    raise ValueError(
                        f"forward dataflow crosses a stage boundary "
                        f"backwards: op {i} ({self.ops[i].type}) at stage "
                        f"{s} consumes {n!r} produced at stage {prod[n]}")
            for n in _real(self.ops[i].output_arg_names()):
                prod[n] = s

    # -- full assignment ---------------------------------------------------
    def _assign_all(self, fwd_assign: Dict[int, int]) -> Dict[int, int]:
        ops = self.ops
        stage_of: Dict[int, int] = dict(fwd_assign)
        var_fwd_stage: Dict[str, int] = {}
        for i in self.fwd_idx:
            for n in _real(ops[i].output_arg_names()):
                var_fwd_stage[n] = fwd_assign[i]
        # min consumer stage for feeds/params (vars with no fwd producer)
        consumer_min: Dict[str, int] = {}
        consumer_stages: Dict[str, Set[int]] = {}
        for i in self.fwd_idx:
            for n in _real(ops[i].input_arg_names()):
                if n not in var_fwd_stage:
                    consumer_min[n] = min(consumer_min.get(n, self.K),
                                          fwd_assign[i])
                    consumer_stages.setdefault(n, set()).add(fwd_assign[i])
        for n, ss in consumer_stages.items():
            v = self.block.var_or_none(n)
            if v is not None and v.is_parameter and len(ss) > 1:
                raise NotImplementedError(
                    f"parameter {n!r} is consumed by stages {sorted(ss)}: "
                    "cross-stage weight sharing is not supported — give "
                    "each stage its own parameter")

        def var_stage(n: str) -> Optional[int]:
            if n in var_fwd_stage:
                return var_fwd_stage[n]
            return consumer_min.get(n)

        producer_stage: Dict[str, int] = dict(var_fwd_stage)
        for i, op in enumerate(ops):
            if i in stage_of or i in self.lr_chain:
                continue
            s: Optional[int] = None
            if i in self.opt_idx:
                s = var_stage(op.input("Param")[0])
                if s is None:
                    raise ValueError(
                        f"optimizer op {op.type} updates "
                        f"{op.input('Param')[0]!r}, which no forward op "
                        "consumes — cannot place it on a stage")
            elif op.has_attr("__fwd_out_slots__"):
                # a grad op: inherit the stage of its forward op (whose
                # outputs ride in the __fwd_out_slots__ input slots)
                cands = [var_stage(n)
                         for slot in op.attr("__fwd_out_slots__", ())
                         for n in _real(op.inputs.get(slot, ()))]
                cands = [c for c in cands if c is not None]
                if cands:
                    s = max(cands)
            if s is None:
                # grad seed / sum / assign combiners: the stage of the
                # var whose gradient they produce
                for out in _real(op.output_arg_names()):
                    if "@GRAD" in out:
                        c = var_stage(out.split("@GRAD")[0])
                        if c is not None:
                            s = c if s is None else max(s, c)
            if s is None:
                cands = [producer_stage[n]
                         for n in _real(op.input_arg_names())
                         if n in producer_stage]
                s = max(cands) if cands else self.K - 1
            stage_of[i] = s
            for n in _real(op.output_arg_names()):
                producer_stage[n] = s
        return stage_of

    # -- emission ----------------------------------------------------------
    def _emit(self, stage_of: Dict[int, int]) -> List[StagePrograms]:
        ops, block, K = self.ops, self.block, self.K
        stages = [StagePrograms(s) for s in range(K)]
        for i in range(len(ops)):
            if i in self.lr_chain:
                continue
            stages[stage_of[i]].op_indices[self._phase(i)].append(i)

        # boundary / stash / feed analysis over F+B ops in program order
        producer: Dict[str, int] = {}  # var -> op index (last F/B writer)
        recv_fwd_use: List[Set[str]] = [set() for _ in range(K)]
        recv_bwd_use: List[Set[str]] = [set() for _ in range(K)]
        stash: List[Set[str]] = [set() for _ in range(K)]
        fwd_feeds: List[Set[str]] = [set() for _ in range(K)]
        bwd_feeds: List[Set[str]] = [set() for _ in range(K)]
        for i, op in enumerate(ops):
            if i in self.lr_chain or i in self.opt_idx:
                continue
            s, p = stage_of[i], self._phase(i)
            for n in _real(op.input_arg_names()):
                j = producer.get(n)
                if j is None:
                    v = block.var_or_none(n)
                    if v is None or v.persistable:
                        continue  # parameter / persistable state
                    (fwd_feeds if p == "F" else bwd_feeds)[s].add(n)
                    continue
                sp, pp = stage_of[j], self._phase(j)
                if sp == s:
                    if pp == "F" and p == "B":
                        stash[s].add(n)
                    continue
                if pp == "F":
                    stages[sp].send_acts.setdefault(n, [])
                    if s not in stages[sp].send_acts[n]:
                        stages[sp].send_acts[n].append(s)
                    stages[s].recv_acts[n] = sp
                    (recv_fwd_use if p == "F" else recv_bwd_use)[s].add(n)
                else:
                    stages[sp].send_grads.setdefault(n, [])
                    if s not in stages[sp].send_grads[n]:
                        stages[sp].send_grads[n].append(s)
                    stages[s].recv_grads[n] = sp
            for n in _real(op.output_arg_names()):
                producer[n] = i

        for st in stages:
            s = st.idx
            st.fwd_feeds = sorted(fwd_feeds[s])
            st.bwd_feeds = sorted(bwd_feeds[s])
            st.stash = sorted(stash[s])
            st.recv_acts_fwd = sorted(recv_fwd_use[s])
            st.recv_acts_bwd = sorted(recv_bwd_use[s])
            st.fwd_fetches = sorted(set(st.send_acts) | stash[s])
            if s == K - 1 and self.loss_name and \
                    self.loss_name not in st.fwd_fetches:
                st.fwd_fetches.append(self.loss_name)
            if s == K - 1:
                st.loss_name = self.loss_name
            st.bwd_fetches = sorted(st.send_grads)
            self._emit_stage(st)
        return stages

    def _ensure_var(self, gb, name: str, src_block=None) -> None:
        if not name or name == EMPTY_VAR or name in gb.vars:
            return
        for blk in (src_block, self.block,
                    self.startup_program.global_block):
            if blk is None:
                continue
            v = blk.var_or_none(name)
            if v is not None:
                gb.vars[name] = Variable.from_dict(gb, v.to_dict())
                return
        gb.create_var(name=name)

    def _clone_ops(self, prog: Program, indices: List[int],
                   rename: Optional[Dict[str, Dict[str, str]]] = None
                   ) -> None:
        """Clone original ops (by index) into ``prog``'s global block;
        ``rename`` optionally remaps input slots per op index:
        ``{slot: {old: new}}`` applied to every listed op."""
        gb = prog.global_block
        for i in indices:
            op = self.ops[i]
            ins = {k: list(v) for k, v in op.inputs.items()}
            if rename:
                for slot, m in rename.items():
                    if slot in ins:
                        ins[slot] = [m.get(n, n) for n in ins[slot]]
            for n in [x for vs in ins.values() for x in vs] + \
                    op.output_arg_names():
                self._ensure_var(gb, n)
            gb.ops.append(Operator(gb, op.type, ins, op.outputs,
                                   dict(op.attrs)))
        prog._version += 1

    def _emit_stage(self, st: StagePrograms) -> None:
        M, block = self.M, self.block
        # forward
        st.fwd_program = Program()
        self._clone_ops(st.fwd_program, st.op_indices["F"])
        for n in st.fwd_fetches + st.recv_acts_fwd + st.fwd_feeds:
            self._ensure_var(st.fwd_program.global_block, n)

        # backward + gradient accumulation
        st.bwd_program = Program()
        self._clone_ops(st.bwd_program, st.op_indices["B"])
        bb = st.bwd_program.global_block
        for n in (st.stash + st.recv_acts_bwd + st.bwd_feeds
                  + list(st.recv_grads) + st.bwd_fetches):
            self._ensure_var(bb, n)
        for i in st.op_indices["O"]:
            op = self.ops[i]
            p, g = op.input("Param")[0], op.input("Grad")[0]
            acc = g + ACC_SUFFIX
            pvar = block.var(p)
            st.param_accs.append((p, g, acc))
            for prog_blk in (bb,):
                prog_blk.create_var(
                    name=acc, shape=pvar.shape, dtype=pvar.dtype,
                    persistable=True)
            scaled = g + "@MBSCALE"
            self._ensure_var(bb, g)
            bb.create_var(name=scaled, shape=pvar.shape, dtype=pvar.dtype)
            bb.append_op("scale", {"X": [g]}, {"Out": [scaled]},
                         {"scale": 1.0 / M, OP_ROLE_ATTR: OpRole.Backward})
            bb.append_op("elementwise_add", {"X": [acc], "Y": [scaled]},
                         {"Out": [acc]},
                         {OP_ROLE_ATTR: OpRole.Backward})

        # optimizer: LR chain + opt ops (Grad -> ACC) + ACC zeroing
        if st.op_indices["O"]:
            st.opt_program = Program()
            ob = st.opt_program.global_block
            self._clone_ops(st.opt_program, sorted(self.lr_chain))
            grad_to_acc = {g: acc for _, g, acc in st.param_accs}
            for p, g, acc in st.param_accs:
                pvar = block.var(p)
                ob.create_var(name=acc, shape=pvar.shape, dtype=pvar.dtype,
                              persistable=True)
            self._clone_ops(st.opt_program, st.op_indices["O"],
                            rename={"Grad": grad_to_acc})
            for p, g, acc in st.param_accs:
                pvar = block.var(p)
                ob.append_op(
                    "fill_constant", {}, {"Out": [acc]},
                    {"shape": [int(d) for d in pvar.shape], "value": 0.0,
                     "dtype": pvar.dtype, OP_ROLE_ATTR: OpRole.Optimize})

        # step-stat registrations (switch_moe aux health) follow their
        # vars onto the stage programs that can fetch them — fresh
        # Program() emission must not silently drop what clone() keeps
        reg = getattr(self.program, "step_stat_vars", None) or {}
        for prog in (st.fwd_program, st.bwd_program, st.opt_program):
            if prog is None:
                continue
            produced = {n for op in prog.global_block.ops
                        for n in _real(op.output_arg_names())}
            for n, key in reg.items():
                if n in produced:
                    prog.step_stat_vars[n] = key

        st.startup_program = self._emit_startup(st)

    def _emit_startup(self, st: StagePrograms) -> Program:
        """Stage startup: the original startup ops whose outputs any of
        this stage's programs reference, plus zero-init of the gradient
        accumulators.  Initializer ops draw by var name (``seed_name``),
        so per-stage init is bit-identical to the single-process run."""
        needed: Set[str] = set()
        for prog in (st.fwd_program, st.bwd_program, st.opt_program):
            if prog is None:
                continue
            for op in prog.global_block.ops:
                needed |= set(_real(op.input_arg_names()))
                needed |= set(_real(op.output_arg_names()))
        sp = Program()
        sp.random_seed = self.startup_program.random_seed
        gb = sp.global_block
        src = self.startup_program.global_block
        for op in src.ops:
            outs = set(_real(op.output_arg_names()))
            if not outs & needed:
                continue
            for n in _real(op.input_arg_names()) + list(outs):
                self._ensure_var(gb, n, src_block=src)
            gb.ops.append(Operator(gb, op.type, op.inputs, op.outputs,
                                   dict(op.attrs)))
        for p, g, acc in st.param_accs:
            pvar = self.block.var(p)
            gb.create_var(name=acc, shape=pvar.shape, dtype=pvar.dtype,
                          persistable=True)
            gb.append_op("fill_constant", {}, {"Out": [acc]},
                         {"shape": [int(d) for d in pvar.shape],
                          "value": 0.0, "dtype": pvar.dtype})
        return sp

    # -- XLA-cost rebalance (PR-7 attribution) -----------------------------
    def _xla_rebalance(self, pp: PipelineProgram, num_stages,
                       batch_hint: int) -> PipelineProgram:
        """One refinement pass: compile each stage's forward program
        AOT, read its real flops from XLA ``cost_analysis`` (the PR-7
        harvest), scale every op's analytic cost by its stage's
        real/analytic ratio, and re-split.  Falls back to the analytic
        split when compilation or costing is unavailable."""
        try:
            measured = xla_stage_flops(pp, batch_hint)
        except Exception:
            return pp
        if not measured or all(m <= 0 for m in measured):
            return pp
        costs = self._op_costs(batch_hint)
        fwd_assign_old = {}
        for i in self.fwd_idx:
            fwd_assign_old[i] = pp.op_stage_assignment[i]
        analytic = [0.0] * pp.num_stages
        for pos, i in enumerate(self.fwd_idx):
            analytic[fwd_assign_old[i]] += costs[pos]
        scale = {}
        for pos, i in enumerate(self.fwd_idx):
            s = fwd_assign_old[i]
            if analytic[s] > 0 and measured[s] > 0:
                scale[i] = measured[s] / analytic[s]
        costs2 = self._op_costs(batch_hint, scale=scale)
        cuts = balanced_cut_points(costs2, num_stages)
        assign = {}
        for pos, i in enumerate(self.fwd_idx):
            s = 0
            for c in cuts:
                if pos >= c:
                    s += 1
            assign[i] = s
        if all(assign[i] == fwd_assign_old[i] for i in self.fwd_idx):
            return pp
        self._validate_forward(assign)
        assignment = self._assign_all(assign)
        stages = self._emit(assignment)
        pp2 = PipelineProgram(stages, self.M, self.loss_name,
                              [assignment.get(i)
                               for i in range(len(self.ops))],
                              sorted(self.lr_chain))
        pp2.validate()
        return pp2


def xla_stage_flops(pp: PipelineProgram, batch_hint: int = 8
                    ) -> List[float]:
    """Real per-stage forward flops from XLA ``cost_analysis`` (the
    PR-7 attribution chain, ``observability/perf.cost_dict``): each
    stage's forward program is AOT-lowered with abstract avals (batch
    ``-1`` dims pinned to ``batch_hint``) and compiled — compile-only,
    nothing executes."""
    import jax
    import numpy as np
    from ..core.lowering import analyze_block, build_block_fn
    from ..core.types import np_dtype
    from ..observability import perf as _perf

    out = []
    for st in pp.stages:
        prog = st.fwd_program
        blk = prog.global_block
        feeds = sorted(set(st.fwd_feeds) | set(st.recv_acts_fwd))
        plan = analyze_block(prog, 0, feeds, list(st.fwd_fetches))

        def aval(name):
            v = blk.var_or_none(name)
            if v is None or v.shape is None:
                raise ValueError(f"no static shape for {name!r}")
            shape = tuple(batch_hint if d == -1 else int(d)
                          for d in v.shape)
            return jax.ShapeDtypeStruct(
                shape, jax.dtypes.canonicalize_dtype(
                    np.dtype(np_dtype(v.dtype or "float32"))))

        feed_avals = [aval(n) for n in feeds]
        state_avals = [aval(n) for n in plan.donated_reads]
        const_avals = [aval(n) for n in plan.const_reads]
        rng = jax.ShapeDtypeStruct((2,), np.uint32)
        fn = build_block_fn(prog, plan, training=True)
        compiled = jax.jit(fn).lower(feed_avals, state_avals, const_avals,
                                     rng).compile()
        cost = _perf.cost_dict(compiled)
        out.append(float(cost.get("flops", 0.0) or 0.0))
    return out
