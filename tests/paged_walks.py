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
