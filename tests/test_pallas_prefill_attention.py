"""The Pallas prefill-attention kernel (interpret mode on CPU) against the
XLA form it replaces on the chip and against a float64 NumPy form: every
key read from the pool's pages where `write_kv` put it, prefix and suffix
alike; and the rule that names the path."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.ops import attention
from xllm_service_tpu.ops.attention import (
    prefill_attention,
    prefill_attention_path,
    write_kv,
)
from xllm_service_tpu.ops.page_walk import prefill_query_tile
from xllm_service_tpu.ops.pallas_prefill_attention import (
    prefill_attention_pallas,
)

LAYERS, LAYER = 2, 1
PS, MAX_PAGES, POOL = 16, 40, 96      # a row of 640 tokens: chunks of 256
S = 32                                 # the bucket

# name -> (query heads, KV heads, lanes held, lanes used, scale, K is V)
HEADS = {
    "group-4": (8, 2, 128, 128, None, False),
    "group-7": (7, 1, 128, 128, None, False),
    "group-8": (16, 2, 128, 128, None, False),
    # the absorbed latent form: one KV head of 640 lanes whose value is the key
    "group-32-latent-640": (32, 1, 640, 576, 192 ** -0.5, True),
    # granite: heads of 64 held at the lane width, scale 1/64
    "head-64-held-at-128": (8, 2, 128, 64, 1 / 64, False),
}
# name -> prefix length: none, one hash block, several blocks whose span
# ends mid-chunk (chunks of 256 tokens: the third holds 28 + the suffix)
PREFIXES = {"cold": 0, "one-hash-block": 32, "span-ends-mid-chunk": 540}
# name -> valid suffix rows: padding rows behind them, or the whole bucket
SEQ_LENS = {"under-the-bucket": 21, "fills-the-bucket": S}
ROWS = [(p, s) for p in PREFIXES for s in SEQ_LENS]       # a batch's rows


def _page_tables(layout: str, rng) -> np.ndarray:
    """[rows, MAX_PAGES]: a row's pages are distinct and never page 0
    (rows may share pages: each is written and read in a pool of its own)."""
    n = len(ROWS)
    if layout == "scattered":
        return np.stack([rng.permutation(POOL - 1)[:MAX_PAGES] + 1
                         for _ in range(n)])
    # runs: a row's first chunk ascends, its second descends, the rest up
    assert layout == "runs"
    row = np.concatenate([np.arange(1, 17), np.arange(48, 32, -1),
                          np.arange(60, 60 + MAX_PAGES - 32)])
    return np.tile(row, (n, 1))


@functools.lru_cache(maxsize=None)
def _case(heads: str, layout: str):
    """One (heads, layout): every row of ROWS through the kernel, the XLA
    form and float64. Cached: each parametrised test reads its row."""
    n_q, n_kv, hd, used, scale, same = HEADS[heads]
    rng = np.random.default_rng(sum(map(ord, heads + layout)))
    lanes = (np.arange(hd) < used).astype(np.float32)
    tables = _page_tables(layout, rng)
    out = {}
    for r, (pname, sname) in enumerate(ROWS):
        pre, seq = PREFIXES[pname], SEQ_LENS[sname]
        total = pre + S

        def rnd(*shape):
            return jnp.asarray(rng.normal(size=shape) * lanes, jnp.float32)

        q = rnd(1, S, n_q, hd)
        k_all = rnd(1, total, n_kv, hd)
        v_all = k_all if same else rnd(1, total, n_kv, hd)
        pt = jnp.asarray(tables[r:r + 1], jnp.int32)
        pre_a, seq_a = (jnp.full((1,), x, jnp.int32) for x in (pre, seq))
        # stale finite data everywhere: what a reused pool holds
        pool = jnp.asarray(rng.normal(size=(LAYERS, 2, POOL, n_kv, PS, hd)),
                           jnp.float32)
        if pre:
            pool = write_kv(pool, LAYER, k_all[:, :pre], v_all[:, :pre], pt,
                            jnp.zeros((1,), jnp.int32), pre_a)
        k, v = k_all[:, pre:], v_all[:, pre:]
        pool = write_kv(pool, LAYER, k, v, pt, pre_a, seq_a)
        kw = {} if scale is None else {"scale": scale}
        xla = prefill_attention(q, k, v, pool, LAYER, pt, pre_a, seq_a, **kw)
        got = prefill_attention_pallas(
            q, pool, jnp.full((1,), LAYER, jnp.int32), pt, pre_a, seq_a,
            interpret=True, **kw)
        want = _float64(q[0], k_all[0], v_all[0], pre, seq,
                        hd ** -0.5 if scale is None else scale)
        out[pname, sname] = (np.asarray(got[0]), np.asarray(xla[0]), want)
    return out


def _float64(q, k, v, pre, seq, scale, softcap=0.0, window=0):
    """Dense causal softmax in float64: q [S, n_q, hd] at positions pre +
    r against keys [pre + S, n_kv, hd] of which pre + seq are real."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    n_q, n_kv = q.shape[1], k.shape[1]
    k, v = (np.repeat(a[:pre + seq], n_q // n_kv, axis=1) for a in (k, v))
    s = np.einsum("qhd,khd->hqk", q[:seq], k) * scale
    if softcap:
        s = softcap * np.tanh(s / softcap)
    qpos = pre + np.arange(seq)[:, None]
    kpos = np.arange(pre + seq)[None, :]
    mask = kpos <= qpos
    if window:
        mask &= qpos - kpos < window
    s = np.where(mask[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", p, v)


@pytest.mark.parametrize("seq", SEQ_LENS)
@pytest.mark.parametrize("prefix", PREFIXES)
@pytest.mark.parametrize("layout", ["scattered", "runs"])
@pytest.mark.parametrize("heads", HEADS)
def test_kernel_matches_the_xla_form_and_float64(heads, layout, prefix, seq):
    """The valid rows equal both forms; the padding rows behind them are
    nobody's (the XLA form's differ too) but finite."""
    got, xla, want = _case(heads, layout)[prefix, seq]
    n = SEQ_LENS[seq]
    np.testing.assert_allclose(got[:n], xla[:n], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[:n], want, rtol=2e-5, atol=2e-5)
    assert np.isfinite(got).all()
    assert not got[:n, :, HEADS[heads][3]:].any()    # the lanes held empty


def _one_row(n_q=8, n_kv=2, hd=128, bucket=64, pre=100, seq=50, seed=3,
             dtype=jnp.float32, poison=False):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, bucket, n_q, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(1, pre + bucket, n_kv, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(1, pre + bucket, n_kv, hd)), dtype)
    pt = jnp.asarray(rng.permutation(POOL - 1)[None, :MAX_PAGES] + 1,
                     jnp.int32)
    pre_a, seq_a = (jnp.full((1,), x, jnp.int32) for x in (pre, seq))
    fill = np.nan if poison else 0.0
    pool = jnp.full((LAYERS, 2, POOL, n_kv, PS, hd), fill, dtype)
    pool = write_kv(pool, LAYER, k[:, :pre], v[:, :pre], pt,
                    jnp.zeros((1,), jnp.int32), pre_a)
    pool = write_kv(pool, LAYER, k[:, pre:], v[:, pre:], pt, pre_a, seq_a)
    return q, k, v, pool, pt, pre_a, seq_a


def _kernel(q, pool, pt, pre, seq, **kw):
    return prefill_attention_pallas(q, pool, jnp.full((1,), LAYER, jnp.int32),
                                    pt, pre, seq, interpret=True, **kw)


@pytest.mark.parametrize("opts", [
    {"softcap": 30.0}, {"window": 40}, {"window": 300},
    {"softcap": 50.0, "window": 70, "scale": 256 ** -0.5}],
    ids=lambda o: "-".join(f"{k}{v:g}" for k, v in o.items()))
def test_gemma2_options_ride_the_kernel(opts):
    """Soft cap and window are static parameters of the kernel, as in
    decode; a window also moves the walk's first chunk (prefix 300: the
    first 256 keys lie below every row's window of 40 or 70)."""
    q, k, v, pool, pt, pre, seq = _one_row(pre=300, seq=50)
    got = _kernel(q, pool, pt, pre, seq, **opts)
    xla = prefill_attention(q, k[:, 300:], v[:, 300:], pool, LAYER, pt, pre,
                            seq, **opts)
    want = _float64(q[0], k[0], v[0], 300, 50,
                    opts.get("scale", 128 ** -0.5),
                    opts.get("softcap", 0.0), opts.get("window", 0))
    np.testing.assert_allclose(got[0, :50], xla[0, :50], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[0, :50], want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bucket,seq,tiles", [(64, 50, 1), (256, 130, 2),
                                              (256, 128, 2), (256, 1, 2)])
def test_tiles_past_seq_len_are_skipped_and_read_zero(bucket, seq, tiles):
    """A bucket is cut in query tiles (8 heads of 128: tiles of 128 rows,
    or the bucket); a tile that holds no valid row fetches nothing and
    writes zeros, and the valid rows do not feel it."""
    assert bucket // prefill_query_tile(bucket, 8, 128, 4) == tiles
    q, k, v, pool, pt, pre, seq_a = _one_row(bucket=bucket, pre=20, seq=seq)
    got = np.asarray(_kernel(q, pool, pt, pre, seq_a))[0]
    want = _float64(q[0], k[0], v[0], 20, seq, 128 ** -0.5)
    np.testing.assert_allclose(got[:seq], want, rtol=2e-5, atol=2e-5)
    tq = bucket // tiles
    dead = -(-seq // tq) * tq
    assert not got[dead:].any() and np.isfinite(got).all()


def test_batch_rows_walk_their_own_pages():
    """Two rows in one call (the engine's prefill sends one; the grid
    takes any): each under its own prefix, length and pages of one pool."""
    rng = np.random.default_rng(11)
    n_q, n_kv, hd, bucket = 8, 2, 128, 64
    pages = rng.permutation(POOL - 1)[:2 * MAX_PAGES] + 1
    pt = jnp.asarray(pages.reshape(2, MAX_PAGES), jnp.int32)
    pre = jnp.asarray([100, 0], jnp.int32)
    seq = jnp.asarray([50, 64], jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, bucket, n_q, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 100 + bucket, n_kv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 100 + bucket, n_kv, hd)), jnp.float32)
    pool = jnp.zeros((LAYERS, 2, POOL, n_kv, PS, hd), jnp.float32)
    pool = write_kv(pool, LAYER, k[:, :100], v[:, :100], pt,
                    jnp.zeros((2,), jnp.int32), pre)
    # row 1 has no prefix: its suffix is its first `bucket` keys
    ks = jnp.stack([k[0, 100:], k[1, :bucket]])
    vs = jnp.stack([v[0, 100:], v[1, :bucket]])
    pool = write_kv(pool, LAYER, ks, vs, pt, pre, seq)
    got = _kernel(q, pool, pt, pre, seq)
    xla = prefill_attention(q, ks, vs, pool, LAYER, pt, pre, seq)
    for b, (p_, n) in enumerate(((100, 50), (0, 64))):
        want = _float64(q[b], k[b], v[b], p_, n, hd ** -0.5)
        np.testing.assert_allclose(got[b, :n], want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got[b, :n], xla[b, :n], rtol=2e-5,
                                   atol=2e-5)


class TestBfloat16Pool:
    """The chip's case: bfloat16 q, K and V to the MXU as they are held."""

    @pytest.mark.parametrize("heads", [(8, 2), (7, 1)])
    def test_error_is_the_operands_rounding(self, heads):
        q, k, v, pool, pt, pre, seq = _one_row(*heads, dtype=jnp.bfloat16)
        got = np.asarray(_kernel(q, pool, pt, pre, seq), np.float32)[0, :50]
        want = _float64(q[0].astype(jnp.float32), k[0].astype(jnp.float32),
                        v[0].astype(jnp.float32), 100, 50, 128 ** -0.5)
        # the output's own bfloat16 rounding (2^-9 of values under ~2) and
        # the probabilities' (one bfloat16 term each, summed over the keys)
        assert np.abs(got - want).max() < 1.5e-2
        assert np.sqrt(((got - want) ** 2).mean()) < 2e-3

    def test_garbage_past_the_context_stays_out(self):
        """NaN in every cell nobody wrote (the pages' tails, the pages
        behind the row's last): the valid rows read none of it, through
        K's mask or V's word guard, and no row's output is NaN."""
        q, k, v, pool, pt, pre, seq = _one_row(dtype=jnp.bfloat16,
                                               poison=True)
        clean = _one_row(dtype=jnp.bfloat16)[3]
        got = np.asarray(_kernel(q, pool, pt, pre, seq), np.float32)
        want = np.asarray(_kernel(q, clean, pt, pre, seq), np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[0, :50], want[0, :50])


# ------------------------------------------------------------ the rule
_TILING = "xla-dense (shape outside the kernel's tiling: "


@pytest.mark.parametrize("backend,interpret,S,hd,heads,kv,dtype,kw,path", [
    ("tpu", False, 512, 128, 28, 4, "bfloat16", {}, "pallas"),
    ("tpu", False, 256, 128, 16, 2, "bfloat16", {}, "pallas"),
    ("tpu", False, 128, 128, 32, 8, "bfloat16", {}, "pallas"),
    ("tpu", False, 3072, 640, 32, 1, "bfloat16", {}, "pallas"),
    ("tpu", False, 512, 128, 28, 4, "float32", {}, "pallas"),
    ("tpu", False, 512, 128, 28, 4, "bfloat16", {"tp": 2},
     "pallas (shard_map model=2)"),
    ("cpu", True, 32, 128, 8, 2, "float32", {}, "pallas"),
    ("cpu", False, 512, 128, 28, 4, "bfloat16", {},
     "xla-dense (cpu backend)"),
    ("tpu", False, 512, 128, 28, 4, "bfloat16", {"pool": False},
     "xla-dense (no pool: the embeddings path)"),
    ("tpu", False, 512, 128, 28, 4, "bfloat16", {"ring": True}, "ring"),
    ("tpu", False, 512, 128, 28, 4, "bfloat16", {"seq_sharded": True},
     "xla-dense (pool sharded over seq)"),
    ("tpu", False, 512, 128, 16, 2, "bfloat16", {"tp": 4},
     "xla-dense (kv heads 2 do not divide over tp=4)"),
    ("tpu", False, 512, 64, 16, 2, "bfloat16", {},
     _TILING + "hd=64 heads=16/2 dtype=bfloat16)"),
    ("tpu", False, 512, 576, 32, 1, "bfloat16", {},
     _TILING + "hd=576 heads=32/1 dtype=bfloat16)"),
    ("tpu", False, 512, 128, 28, 4, "float16", {},
     _TILING + "hd=128 heads=28/4 dtype=float16)"),
    ("tpu", False, 100, 128, 28, 4, "bfloat16", {},
     _TILING + "no query tile for S=100 heads=28 hd=128)"),
    ("tpu", False, 8, 128, 28, 4, "bfloat16", {},
     _TILING + "no query tile for S=8 heads=28 hd=128)"),
    ("tpu", False, 8, 128, 28, 4, "float32", {}, "pallas"),
    ("tpu", False, 512, 4096, 64, 8, "bfloat16", {},
     _TILING + "no query tile for S=512 heads=64 hd=4096)"),
])
def test_prefill_attention_path_names_the_recorded_string(
        backend, interpret, S, hd, heads, kv, dtype, kw, path):
    """The one decision over what the code observes, and the exact string
    `/stats`.attention_paths carries under every `prefill_install*`."""
    assert prefill_attention_path(backend, interpret, S, hd, heads, kv,
                                  dtype, **kw) == path


@pytest.mark.parametrize("S,heads,hd,itemsize,tq", [
    (512, 28, 128, 2, 128),      # qwen25-7b-int8: 4 tiles in its 512 bucket
    (256, 16, 128, 2, 128),      # qwen25-3b-bf16: 2 tiles behind the prefix
    (128, 32, 128, 2, 128),      # granite-4.0-h-micro
    (512, 32, 640, 2, 32),       # kanana-2's latent: 1024 rows a tile
    (32, 32, 640, 4, 16),
    (64, 8, 128, 4, 64),
    (48, 8, 128, 2, 16),
    (8, 8, 128, 4, 8), (8, 8, 128, 2, 0), (100, 8, 128, 4, 0),
])
def test_query_tile_follows_the_shape(S, heads, hd, itemsize, tq):
    assert prefill_query_tile(S, heads, hd, itemsize) == tq


def test_dispatcher_records_the_path_and_runs_the_kernel(monkeypatch):
    """`prefill_attention` under XLLM_PALLAS_INTERPRET=1 takes the kernel
    where it took the XLA form, gives the same valid rows, and both words
    are in the program's record."""
    q, k, v, pool, pt, pre, seq = _one_row()
    rec = {}

    def run(label):
        with attention.trace_program(label, rec):
            return prefill_attention(q, k[:, 100:], v[:, 100:], pool, LAYER,
                                     pt, pre, seq)

    xla = run("plain")
    monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "1")
    got = run("kernel")
    assert rec == {"plain": {"prefill_attention": "xla-dense (cpu backend)"},
                   "kernel": {"prefill_attention": "pallas"}}
    np.testing.assert_allclose(got[0, :50], xla[0, :50], rtol=2e-5,
                               atol=2e-5)
    # no pool: the embeddings path stays plain whatever the backend says
    with attention.trace_program("embed", rec):
        prefill_attention(q, k[:, 100:], v[:, 100:], None, None, None,
                          jnp.zeros((1,), jnp.int32), seq)
    assert rec["embed"] == {
        "prefill_attention": "xla-dense (no pool: the embeddings path)"}


def test_kernel_runs_per_head_shard_under_a_model_mesh(monkeypatch):
    """Tensor parallel: each device runs the kernel on its own heads of q
    and of the pool (`_on_head_shards`), as decode does."""
    from jax.sharding import NamedSharding

    from xllm_service_tpu.parallel.mesh import MeshConfig, build_mesh
    from xllm_service_tpu.parallel.sharding import KV_PAGES_SPEC

    monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "1")
    mesh = build_mesh(MeshConfig(model=2), devices=jax.devices()[:2])
    q, k, v, pool, pt, pre, seq = _one_row()
    want = prefill_attention_pallas(
        q, pool, jnp.full((1,), LAYER, jnp.int32), pt, pre, seq,
        interpret=True)
    pool_s = jax.device_put(pool, NamedSharding(mesh, KV_PAGES_SPEC))
    rec = {}

    def step(q, k, v, pool):
        with attention.trace_program("prog", rec, mesh):
            return prefill_attention(q, k, v, pool, LAYER, pt, pre, seq)

    got = jax.jit(step)(q, k[:, 100:], v[:, 100:], pool_s)
    assert rec == {"prog": {
        "prefill_attention": "pallas (shard_map model=2)"}}
    np.testing.assert_allclose(got[0, :50], want[0, :50], rtol=2e-5,
                               atol=2e-5)
