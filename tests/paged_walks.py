"""Cases for the paged decode kernel's walk over a slot's live blocks
(tests/test_decode_plane.py: f32 pool; tests/test_quant_serving.py: int8)."""
import jax
import jax.numpy as jnp
import numpy as np

# the kernel walks a slot's live blocks in chunks of
# AK._DECODE_CHUNK_BLOCKS (clipped to the table): contexts by where they
# end against a chunk of ``ct`` tokens and a table of ``total``
WALKS = {
    "ends_on_a_chunk": lambda ct, total: [ct, min(2 * ct, total), ct],
    "one_past_a_chunk": lambda ct, total: [min(ct + 1, total),
                                           min(2 * ct + 1, total), 1],
    "one_token_idle_slots": lambda ct, total: [1, 1, 1, 1],
    "the_full_table": lambda ct, total: [total, total, total],
    # every kind in adjacent slots: what slot s leaves in the buffers,
    # and the fetch it starts for slot s + 1, must not reach s + 1's rows
    "all_of_these_in_adjacent_slots": lambda ct, total: [
        1, ct, min(ct + 1, total), total, 3, 1, ct - 1, total - 1,
        min(2 * ct, total), 1, total],
}


def dense_reference(q, kc, vc, bt, cl, layer):
    S, H, D = q.shape
    out = np.zeros((S, H, D), np.float32)
    for s in range(S):
        n = int(cl[s])
        k = np.asarray(kc[layer, bt[s]]).reshape(-1, H, D)[:n]
        v = np.asarray(vc[layer, bt[s]]).reshape(-1, H, D)[:n]
        sc = np.einsum("hd,thd->ht", np.asarray(q[s]), k) / np.sqrt(D)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out[s] = np.einsum("ht,thd->hd", p / p.sum(-1, keepdims=True), v)
    return out


def walk_case(rng, contexts, MB, bs=4, H=4, D=64, L=3, poison=False):
    """A pool and tables for ``contexts``: a one-token slot's table is all
    trash block 0 (an idle decode slot); every other slot's table is full
    of blocks of its own, also past its context — with ``poison`` those
    dead entries name a block of NaN, which nothing may read."""
    S = len(contexts)
    N = 2 + S * MB
    kc = rng.randn(L, N, bs, H * D).astype("float32")
    vc = rng.randn(L, N, bs, H * D).astype("float32")
    bt = (2 + rng.permutation(S * MB)).reshape(S, MB).astype("int32")
    for s, n in enumerate(contexts):
        if n == 1:
            bt[s, :] = 0
        elif poison:
            bt[s, -(-n // bs):] = 1
    if poison:
        kc[:, 1], vc[:, 1] = np.nan, np.nan
    q = jnp.asarray(rng.randn(S, H, D).astype("float32"))
    return (q, jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(bt),
            jnp.asarray(np.asarray(contexts, "int32")))


def eqns_under(jaxpr):
    """Every equation under ``jaxpr``, those of scanned bodies and jitted
    calls among them (a step program's ``pallas_call``s and their grids)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns_under(sub)


def grouped_glu_on(one_chip, tokens, k, experts, d, f, act, out_dtype,
                   layers=None):
    """``kernels/moe.py grouped_glu`` alone on the plan of ``tokens`` tokens
    at top-``k`` (shapes only), compiled for the described chip: (the
    compiled program's text, its one ``pallas_call`` equation, the kernel's
    own equations' names).  ``layers``: the matrices are a stack and the
    layer a traced scalar."""
    from paddle_tpu.kernels import moe

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tile = moe.row_tile(tokens, jnp.bfloat16)
    R = moe.plan_rows(tokens, k, experts, tile)
    i32 = jnp.int32
    plan = moe.GroupPlan(sds((R,), i32), sds((tokens, k), i32),
                         sds((R // tile,), i32), sds((1,), i32),
                         sds((experts,), i32), sds((3,), i32))
    lead = () if layers is None else (layers,)
    args = [sds((R, d), jnp.bfloat16)] + [
        sds(lead + shape, jnp.bfloat16)
        for shape in ((experts, d, f), (experts, d, f), (experts, f, d))]
    if layers is not None:
        args.append(sds((), i32))

    def fn(x, wg, wu, wd, plan, layer=None):
        return moe.grouped_glu(x, wg, wu, wd, plan, tile, act=act,
                               layer=layer, out_dtype=out_dtype)

    args.insert(4, plan)
    text = jax.jit(fn).lower(*args).compile().as_text()
    call, = [e for e in eqns_under(jax.make_jaxpr(fn)(*args).jaxpr)
             if e.primitive.name == "pallas_call"]
    inside = {e.primitive.name for e in eqns_under(call.params["jaxpr"])}
    return text, call, inside


def moe_walks(act):
    """(expert walks, tile walks) lowered so far of ``grouped_glu`` with the
    gate ``act``: the counters that say which walk a plan's tile chose."""
    from paddle_tpu.kernels import moe
    from paddle_tpu.observability import stats
    d = stats.to_dict()
    return tuple(d.get(f"moe.grouped_{moe.ACTS[act][1]}_{w}_walks", 0)
                 for w in ("expert", "tile"))


def check_both_walks_on(one_chip, step_tokens, rung, k, experts, d, f, act,
                        out_dtype, layers=None):
    """Mosaic accepts the expert walk at a cell's widths and its largest
    rung's rows, inside the VMEM the kernel asks for (the compile raises
    otherwise); the step's plan keeps the walk it had: a tile a grid step,
    blocks by the tile→expert map, no copy of the kernel's own."""
    from paddle_tpu.kernels import moe

    def walks():
        return moe_walks(act)

    name = f"moe_grouped_{moe.ACTS[act][1]}"
    before = walks()
    text, call, inside = grouped_glu_on(one_chip, rung, k, experts, d, f,
                                        act, out_dtype, layers)
    assert "tpu_custom_call" in text and call.params["name"] == name
    assert tuple(call.params["grid_mapping"].grid) == (experts,)
    assert "dma_start" in inside and "dma_wait" in inside
    after = walks()
    assert after[0] > before[0] and after[1] == before[1]
    text, call, inside = grouped_glu_on(one_chip, step_tokens, k, experts, d,
                                        f, act, out_dtype, layers)
    assert "tpu_custom_call" in text and call.params["name"] == name
    rows = moe.plan_rows(step_tokens, k, experts, 16)
    gm = call.params["grid_mapping"]
    assert tuple(gm.grid) == (rows // 16,)
    assert gm.num_index_operands == 2 and gm.num_scratch_operands == 0
    assert [tuple(b.block_size for b in m.block_shape)
            for m in gm.block_mappings] == [
        (16, d), (1, d, f), (1, d, f), (1, f, d), (16, d)]
    assert "dma_start" not in inside
    step = walks()
    assert step[0] == after[0] and step[1] > after[1]
