"""The Scheduler's slot vmap folds into the packed kernels' rows.

``vusa_packed_matmul`` and ``vusa_fused_mlp_matmul`` carry a vmap rule of
their own (``vusa_packed.fold_slot_vmap``): when only ``x`` carries the
vmapped axis, the slots' rows run as one ``pallas_call`` on ``(slots*rows,
K)``, where ``pallas_call``'s own rule would add a grid axis and rebuild the
pack once per slot.  In interpret mode the folded call must equal the
per-slot calls bit for bit (the Scheduler's streams are bit-identical to
``Engine.generate``'s, DESIGN.md §11, §13); a batched pack keeps the grid
axis; and the Scheduler reports how many calls of a decode step folded.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import requires_devices
from repro.configs import get_smoke_config
from repro.core.pruning import prune_tree
from repro.kernels.ops import pack_linear_rows, pack_linear_rows_t
from repro.kernels.vusa_packed import (
    calls_repeat,
    fold_tally,
    vusa_fused_mlp_matmul,
    vusa_packed_matmul,
)
from repro.launch.mesh import make_serve_mesh
from repro.models import build_model
from repro.serve import Engine, Request, Scheduler, ServeConfig

D, FF, M, A, K_BLK, SLOTS = 256, 384, 128, 16, 128, 4

CASES = [
    ("packed", "dense", "vusa_packed_matmul"),
    ("packed", "int8", "vusa_packed_matmul"),
    ("packed", "int4", "vusa_packed_matmul"),
    ("packed", "int8", "vusa_packed_matmul_head"),
    ("fused", "dense", None),
    ("fused", "int8", None),
    ("fused", "int4", None),
]
CASE_IDS = [f"{k}-{v}" + ("-head" if n and n.endswith("head") else "") for k, v, n in CASES]


def _sparse(rng, k, c, sparsity=0.85):
    return (rng.normal(size=(k, c)) * (rng.random((k, c)) > sparsity)).astype(np.float32)


def _kernel(kind, value_dtype, name):
    """``(call, pack)``: ``call(x, *pack)`` is the kernel on (B, D) rows."""
    rng = np.random.default_rng(7)
    kw = dict(m=M, k_blk=K_BLK, interpret=True, value_dtype=value_dtype)
    if kind == "packed":
        p = pack_linear_rows(_sparse(rng, D, FF), m=M, a=A, value_dtype=value_dtype)

        def call(x, v, q, s):
            return vusa_packed_matmul(x, v, q, s, name=name, **kw)

        return call, (p.values, p.positions, p.scales)
    g, u = (pack_linear_rows(_sparse(rng, D, FF), m=M, a=A, value_dtype=value_dtype)
            for _ in range(2))
    d = pack_linear_rows_t(_sparse(rng, FF, D), m=M, a=A, value_dtype=value_dtype)

    def call(x, gv, gp, uv, up, dv, dp, gs, us, ds):
        return vusa_fused_mlp_matmul(x, gv, gp, uv, up, dv, dp, gs, us, ds, **kw)

    return call, (g.values, g.positions, u.values, u.positions, d.values, d.positions,
                  g.scales, u.scales, d.scales)


def _x(rows):
    rng = np.random.default_rng(rows)
    return jnp.asarray(rng.normal(size=(SLOTS, rows, D)), jnp.float32)


def _pallas_x_shapes(jaxpr):
    """The shape of the first operand of every ``pallas_call`` in ``jaxpr``,
    sub-programs included."""
    shapes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            shapes.append(tuple(eqn.invars[0].aval.shape))
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, tuple) else (v,):
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's Jaxpr
                if hasattr(sub, "eqns"):
                    shapes += _pallas_x_shapes(sub)
    return shapes


@pytest.mark.parametrize("rows", [1, 3], ids=["decode", "verify3"])
@pytest.mark.parametrize("kind, value_dtype, name", CASES, ids=CASE_IDS)
def test_slot_vmap_matches_per_slot_calls_bitwise(kind, value_dtype, name, rows):
    call, pack = _kernel(kind, value_dtype, name)
    x = _x(rows)
    per_slot = jnp.stack([call(x[i], *pack) for i in range(SLOTS)])
    folded = jax.jit(jax.vmap(call, in_axes=(0,) + (None,) * len(pack)))(x, *pack)
    assert folded.shape == per_slot.shape
    np.testing.assert_array_equal(np.asarray(folded), np.asarray(per_slot))


@pytest.mark.parametrize("rows", [1, 3], ids=["decode", "verify3"])
@pytest.mark.parametrize("kind, value_dtype, name", CASES, ids=CASE_IDS)
def test_slot_vmap_is_one_call_on_the_slots_rows(kind, value_dtype, name, rows):
    call, pack = _kernel(kind, value_dtype, name)
    fn = jax.vmap(call, in_axes=(0,) + (None,) * len(pack))
    with fold_tally() as tally:
        jaxpr = jax.make_jaxpr(fn)(_x(rows), *pack)
    assert _pallas_x_shapes(jaxpr.jaxpr) == [(SLOTS * rows, D)]
    assert (tally.folded, tally.fallback) == (1, 0)


@pytest.mark.parametrize("kind", ["packed", "fused"])
def test_batched_pack_falls_back_to_the_grid_axis(kind):
    """A pack that carries the vmapped axis (one per slot) takes
    ``pallas_call``'s own rule, as ``jax.vmap`` of the unwrapped kernel."""
    call, pack = _kernel(kind, "int8", "vusa_packed_matmul")
    kernel = (vusa_packed_matmul if kind == "packed" else vusa_fused_mlp_matmul).__wrapped__
    kw = dict(m=M, k_blk=K_BLK, interpret=True, value_dtype="int8")
    per_slot = jax.tree.map(lambda a: jnp.stack([a] * SLOTS), pack)
    x = _x(1)
    with fold_tally() as tally:
        got = jax.vmap(call)(x, *per_slot)
    want = jax.vmap(lambda *a: kernel(*a, **kw))(x, *per_slot)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (tally.folded, tally.fallback) == (0, 1)


def test_calls_repeat_counts_a_scan_body_once_per_step():
    call, pack = _kernel("packed", "int8", "vusa_packed_matmul")

    def step(x):  # three layers of one call each, then one more call
        with calls_repeat(3):
            x, _ = jax.lax.scan(lambda c, _: (call(c, *pack)[:, :D], None), x, None, length=3)
        return call(x, *pack)

    with fold_tally() as tally:
        jax.make_jaxpr(jax.vmap(step))(_x(1))
    assert (tally.folded, tally.fallback) == (4, 0)


@pytest.fixture(scope="module")
def vusa_pruned():
    cfg = get_smoke_config("vusa_edge")
    params = prune_tree(build_model(cfg).init(jax.random.key(0)), 0.85)
    return cfg, params


# (ServeConfig fields, mesh, decode steps a segment step runs): a
# speculative round drafts DRAFT_K steps and verifies once
DRAFT_K = 2
SERVE_CASES = {
    "slot_pool": (dict(), None, 1),
    "paged": (dict(page_size=8), None, 1),
    "paged_sharded": (dict(page_size=8), "1,2", 1),
    "speculative": (dict(speculative=True, draft_k=DRAFT_K), None, DRAFT_K + 1),
    "speculative_paged": (dict(speculative=True, draft_k=DRAFT_K, page_size=8), None, DRAFT_K + 1),
}


@pytest.mark.parametrize("case", [
    pytest.param(c, marks=requires_devices(2)) if SERVE_CASES[c][1] else c for c in SERVE_CASES
])
def test_scheduler_reports_one_folded_call_per_matrix(vusa_pruned, case):
    """Per decode step: wq, wk, wv, wo and the fused MLP in every layer, the
    head once, all folded into the slots' rows: through the slot pool, the
    paged arena, the window-sharded appliers and speculative rounds."""
    kw, mesh, steps = SERVE_CASES[case]
    cfg, params = vusa_pruned
    sc = ServeConfig(max_len=64, packed_weights="all", packed_values="int8", **kw)
    eng = Engine(cfg, params, sc, mesh=make_serve_mesh(mesh) if mesh else None)
    sched = Scheduler(eng, slots=2, segment=4)
    rng = np.random.default_rng(0)
    sched.run([Request(prompt=rng.integers(1, 100, n).astype(np.int32), max_new=5, seed=i)
               for i, n in enumerate((5, 9, 12))])
    st = sched.stats()
    want = (steps * (5 * cfg.n_layers + 1), 0)
    assert (st["packed_calls_folded"], st["packed_calls_fallback"]) == want
