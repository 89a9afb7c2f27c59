"""``ops/routed_rows.py`` on the CPU: dispatch and combine against the
plain ``take`` / ``.at[].add`` form (what ``RoutedExpertsFFN`` wrote
before PR 47, transposed by JAX itself) as the oracle, values and every
gradient, in both forms ``combine_form`` can pick (the kernel
interpreted), alone and inside the layer — at ``top_k > held``, an expert with no rows, ``n_here > cap``,
rows past the last group, both row factors and the six decoder cells'
(top_k, held / experts) at tiny sizes — and the rule itself at the six
cells' real shapes."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.ops import routed_rows as rr
from dinov3_tpu.ops.ffn import RoutedExpertsFFN, routed_rows_capacity

FORMS = ("sorted", "scatter")
f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731


def _force(monkeypatch, form):
    monkeypatch.setattr(rr, "combine_form", lambda *a, **k: form)


def _plain(monkeypatch):
    """The oracle: the two movements as plain indexing, JAX's transposes."""
    monkeypatch.setattr(rr, "dispatch_rows", lambda x, lists, _: jnp.take(
        x, lists.token, axis=0))
    monkeypatch.setattr(
        rr, "combine_rows", lambda src, lists, n, dtype, _: jnp.zeros(
            (n,) + src.shape[1:], jnp.float32).at[lists.token].add(
                src).astype(dtype))


# ------------------------------------------------- the primitives alone


def _routing(n, k, cap, n_here, seed=0):
    """(order, kept): ``n_here`` pairs at random in four groups, in pair
    order inside a group, then the pairs routed elsewhere."""
    rng = np.random.default_rng(seed)
    here = rng.permutation(n * k)[:n_here]
    groups = np.array_split(here, 4)
    away = np.setdiff1d(np.arange(n * k), here)
    order = np.concatenate([np.sort(g) for g in groups] + [away])[:cap]
    return (jnp.asarray(order, jnp.int32),
            jnp.arange(cap) < min(n_here, cap))


@pytest.mark.parametrize("n_here", [0, 100, 256, 300],
                         ids=["no_rows", "rows_past_the_last_group",
                              "a_full_buffer", "more_pairs_than_rows"])
def test_lists_sort_the_kept_rows_by_token(n_here):
    n, k, cap = 128, 4, 256
    order, kept = _routing(n, k, cap, n_here)
    assert rr.row_lists(order, kept, n, k, "scatter")[1:] == (None,) * 3
    # "sorted": the kept rows by token, then the rest as token ``n``; a
    # tile's rows start where its first token's do
    by_token = rr.row_lists(order, kept, n, k, "sorted")
    token = np.where(kept, np.asarray(order) // k, n)
    np.testing.assert_array_equal(by_token.token, np.asarray(order) // k)
    np.testing.assert_array_equal(
        np.asarray(by_token.sorted_token).reshape(-1), np.sort(token))
    np.testing.assert_array_equal(token[np.asarray(by_token.perm)],
                                  np.sort(token))
    np.testing.assert_array_equal(by_token.starts, np.searchsorted(
        np.sort(token), np.arange(0, n + 1, 128)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("form", FORMS)
def test_each_primitive_and_its_transpose_match_plain_indexing(form, dtype):
    n, k, cap, d = 128, 4, 256, 128
    order, kept = _routing(n, k, cap, 200)
    lists = rr.row_lists(order, kept, n, k, form)
    ks = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(ks[0], (n, d), dtype)
    dy = jax.random.normal(ks[1], (n, d), dtype)
    # what the experts' block leaves past the last group: zeros
    src = jnp.where(kept[:, None], jax.random.normal(ks[2], (cap, d)), 0.0)
    d_rows = jnp.where(kept[:, None], jax.random.normal(
        ks[3], (cap, d)), 0.0).astype(dtype)

    rows, back = jax.vjp(lambda x: rr.dispatch_rows(x, lists, True), x)
    np.testing.assert_array_equal(f32(rows), f32(x)[np.asarray(lists.token)])
    assert rows.dtype == dtype
    dx, = back(d_rows)
    want = jnp.zeros((n, d), jnp.float32).at[lists.token].add(f32(d_rows))
    assert dx.dtype == dtype
    np.testing.assert_allclose(f32(dx), f32(want.astype(dtype)),
                               rtol=2e-2, atol=2e-2)

    y, back = jax.vjp(lambda s: rr.combine_rows(s, lists, n, dtype, True),
                      src)
    want = jnp.zeros((n, d), jnp.float32).at[lists.token].add(src)
    assert y.dtype == dtype
    np.testing.assert_allclose(f32(y), f32(want.astype(dtype)), rtol=1e-5,
                               atol=1e-5)
    d_src, = back(dy)  # the rule's signature: the source's type, exactly dy
    assert d_src.dtype == src.dtype
    np.testing.assert_array_equal(f32(d_src), f32(dy)[np.asarray(lists.token)])


def test_no_fill_mode_select_and_no_float32_cotangent_plane():
    """Both passes of both primitives in a bfloat16 model: every gather
    and scatter promises its indices in bounds (no clamp-and-select over a
    plane), and the cotangent's gather is of bfloat16 rows."""
    n, k, cap, d = 128, 4, 256, 128
    order, kept = _routing(n, k, cap, 200)
    for form in FORMS:
        lists = rr.row_lists(order, kept, n, k, form)

        def both(x, src, dy, d_rows):
            rows, db = jax.vjp(lambda x: rr.dispatch_rows(x, lists, True), x)
            y, cb = jax.vjp(lambda s: rr.combine_rows(
                s, lists, n, jnp.bfloat16, True), src)
            return rows, y, db(d_rows), cb(dy)

        b, f = jnp.bfloat16, jnp.float32
        jaxpr = jax.make_jaxpr(both)(
            jnp.zeros((n, d), b), jnp.zeros((cap, d), f),
            jnp.zeros((n, d), b), jnp.zeros((cap, d), b))
        moved = [e for e in _eqns(jaxpr.jaxpr)
                 if e.primitive.name in ("gather", "scatter-add", "scatter")]
        assert moved, form
        for e in moved:
            assert "PROMISE_IN_BOUNDS" in str(e.params["mode"]), (form, e)
        wide = [e.outvars[0].aval.dtype for e in moved
                if e.primitive.name == "gather"
                and e.outvars[0].aval.shape == (cap, d)]
        # dispatch and the cotangent's; "sorted" puts each pass's source
        # into token order besides, in the source's type
        want = [b, b] + [f, b] * (form == "sorted")  # + each pass's sort
        assert sorted(map(str, wide)) == sorted(
            str(jnp.dtype(t)) for t in want), (form, wide)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


# ------------------------------------------------------ inside the layer

# experts, top_k, shards, rows_factor, the selection bias of the first
# experts (the held ones come first: shard 0)
LAYERS = {
    "top_k_over_held": (8, 4, 4, 2.0, ()),
    "an_expert_with_no_rows": (8, 2, 2, 2.0, (0.0, -10.0)),
    "more_pairs_than_rows": (8, 2, 2, 0.25, (5.0, 4.0)),
    "rows_past_the_last_group": (8, 2, 2, 2.0, (-10.0, -10.0, -10.0)),
    "rows_factor_4": (16, 4, 4, 4.0, ()),
    # the six cells' (top_k, held / experts) and row factors
    "smallthinker-ep4-pretrain-16k": (64, 6, 4, 2.0, ()),
    "kanana2-ep8-pretrain-16k": (128, 6, 8, 4.0, ()),
    "qwen3-next-ep16-pretrain-8k": (512, 10, 16, 4.0, ()),
    "keye-vl2-ep8-pretrain-16k": (128, 8, 8, 2.0, ()),
    "lfm2-ep8-pretrain-8k": (64, 4, 8, 2.0, ()),
    "kimi-linear-ep32-pretrain-8k": (256, 8, 32, 2.0, ()),
}


def _layer_results(layer, params, x):
    def f(p, x):
        y, aux = layer.apply({"params": p}, x)
        return jnp.sum(jnp.sin(y.astype(jnp.float32))), (y, aux)
    (_, (y, aux)), grads = jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True)(params, x)
    return y, aux, grads


def _assert_layers_agree(layer, x, bias, monkeypatch, tol):
    params = nn.meta.unbox(layer.init(jax.random.key(2), x)["params"])
    e = params["router_bias"].shape[0]
    params = dict(params, router_bias=jnp.asarray(
        tuple(bias) + (0.0,) * (e - len(bias)), jnp.float32))
    found = {}
    for form in FORMS:
        with monkeypatch.context() as m:
            _force(m, form)
            found[form] = _layer_results(layer, params, x)
    with monkeypatch.context() as m:
        _plain(m)
        y0, aux0, grads0 = _layer_results(layer, params, x)
    for form, (y, aux, grads) in found.items():
        for key in ("rows", "capacity", "overflow", "load_max_over_mean"):
            assert float(aux[key]) == float(aux0[key]), (form, key)
        np.testing.assert_array_equal(aux["choice"], aux0["choice"])
        assert y.dtype == y0.dtype
        np.testing.assert_allclose(f32(y), f32(y0), rtol=tol, atol=tol)
        (p, dx), (p0, dx0) = grads, grads0
        for name in ("w12", "w3", "router"):
            a, b = f32(p[name]), f32(p0[name])
            assert np.linalg.norm(a - b) <= tol * max(
                np.linalg.norm(b), 1e-6), (form, name)
        assert np.linalg.norm(f32(dx) - f32(dx0)) <= tol * max(
            np.linalg.norm(f32(dx0)), 1e-6), form
    return aux0


@pytest.mark.parametrize("case", list(LAYERS))
def test_layer_matches_the_plain_form_in_every_form(case, monkeypatch):
    """Float32 rows (``ragged_dot``'s path; "sorted"'s kernel interpreted):
    what differs between the forms is the order of a token's float32
    additions."""
    e, k, shards, factor, bias = LAYERS[case]
    n = 512 if case == "more_pairs_than_rows" else 128
    x = jax.random.normal(jax.random.key(1), (n, 128), jnp.float32)
    layer = RoutedExpertsFFN(16, e, k, shards=shards, shard=0,
                             rows_factor=factor, dtype=jnp.float32,
                             interpret=True)
    aux = _assert_layers_agree(layer, x, bias, monkeypatch, tol=1e-5)
    if case == "more_pairs_than_rows":  # every pair is routed here
        assert float(aux["overflow"]) == n * 2 - float(aux["capacity"]) > 0
    elif case == "rows_past_the_last_group":
        assert 0 <= float(aux["rows"]) < float(aux["capacity"])
    else:
        assert float(aux["overflow"]) == 0 < float(aux["rows"])


@pytest.mark.parametrize("case", ["top_k_over_held", "more_pairs_than_rows"])
def test_layer_on_the_interpreted_kernels_matches_in_every_form(
        case, monkeypatch):
    """Bfloat16 rows through the kernels (interpreted): the kernels round
    the cotangent they are given where the plain form hands them float32,
    and the gather adds ``dx`` in float32 where the scatter adds in
    bfloat16: roundings apart, a part left out reads 0.1-1."""
    e, k, shards, factor, bias = LAYERS[case]
    x = jax.random.normal(jax.random.key(1), (256, 128), jnp.bfloat16)
    layer = RoutedExpertsFFN(128, e, k, shards=shards, shard=0,
                             rows_factor=factor, interpret=True)
    _assert_layers_agree(layer, x, bias, monkeypatch, tol=3e-2)


# ---------------------------------------------------------------- the rule

# tokens, top_k, experts, held, rows_factor, D; rows, pairs a buffer row
CELLS = {
    "smallthinker-ep4-pretrain-16k": (16384, 6, 64, 16, 2.0, 2560, 49152, 2),
    "kanana2-ep8-pretrain-16k": (16384, 6, 128, 16, 4.0, 2048, 49152, 2),
    "qwen3-next-ep16-pretrain-8k": (16384, 10, 512, 32, 4.0, 2048, 40960, 4),
    "keye-vl2-ep8-pretrain-16k": (16384, 8, 128, 16, 2.0, 2048, 32768, 4),
    "lfm2-ep8-pretrain-8k": (32768, 4, 64, 8, 2.0, 2048, 32768, 4),
    "kimi-linear-ep32-pretrain-8k": (16384, 8, 256, 8, 2.0, 2304, 8192, 16),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_every_cell_combines_by_its_buffer_whatever_its_pairs_a_row(cell):
    """ISSUE 47 expected a gather through an inverse list to win at 2
    pairs a buffer row and lose at 16; on the chip it lost at every ratio
    (``ops/routed_rows.py``), so the form follows from what can run."""
    n, k, e, held, factor, d, cap, ratio = CELLS[cell]
    assert routed_rows_capacity(n, k, e, held, factor) == cap
    assert n * k == ratio * cap
    assert rr.combine_form(n, cap, d, interpret=False) == "sorted"  # a TPU
    assert rr.combine_form(n, cap, d) == "scatter"                  # here
    # the kernel's needs: whole lanes, whole chunks of rows, whole tiles
    for shape in ((n, cap, d + 8), (n, cap + 8, d), (n + 8, cap, d)):
        assert rr.combine_form(*shape, interpret=False) == "scatter"
