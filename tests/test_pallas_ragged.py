"""Ragged fused encode + attention (ops/pallas_ragged.py) vs the
unpack-then-dense path, under the tests/test_packed.py property regime:
interior holes, pad rows, capacity < batch, fill rates from empty to
full, nonzero PAD indices, per-shard packing. The jnp twin is exercised
everywhere (it is the non-TPU fallback); both Pallas kernels — the
forward and the custom-VJP recompute backward — run in interpreter mode
on CPU, single-shard, flat multi-shard, multi-tile, and shard_mapped
over the 8-virtual-device mesh. TestFusedBackward owns the train-path
acceptance: five-param gradient parity across the regime, dropout-mask
bit-match between the fused pair and the twin, bf16 smoke, and the
no-per-slot-residuals contract (vjp-closure assertion). Trainer
integration covers packed train/eval and all four predict tiers, the
and the zero-post-warmup-compiles guards on predict AND the fused
train step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.data import packed as packed_lib
from code2vec_tpu.models import functional
from code2vec_tpu.ops import pallas_ragged

from tests.test_packed import random_plane_batch
from tests.test_stage_batches import make_trainer

# forced kernels reached through functional/Trainer carry no per-call
# interpret flag: the fixture turns the interpreter on for this module
pytestmark = pytest.mark.usefixtures('pallas_interpret')


def small_params(rng_seed=0, token_vocab=32, path_vocab=16,
                 target_vocab=16, token_dim=8, path_dim=6, code_dim=24):
    return functional.init_params(
        jax.random.PRNGKey(rng_seed), token_vocab_size=token_vocab,
        path_vocab_size=path_vocab, target_vocab_size=target_vocab,
        token_dim=token_dim, path_dim=path_dim, code_dim=code_dim)


def dense_reference(params, batch):
    """The unpack-then-dense ground truth: the packed round trip is
    BIT-exact (tests/test_packed.py), so encoding the original planes IS
    encoding the unpacked wire."""
    return functional.encode(params, batch.source, batch.path,
                             batch.target, batch.mask)


def ragged(params, packed, max_contexts, token_pad, path_pad, **kw):
    return pallas_ragged.ragged_encode(
        params.token_embedding, params.path_embedding, params.transform,
        params.attention, jnp.asarray(packed.ctx),
        jnp.asarray(packed.count), max_contexts=max_contexts,
        token_pad=token_pad, path_pad=path_pad, **kw)


def assert_encode_close(got, want, rtol=2e-5, atol=1e-6):
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=rtol, atol=atol, err_msg='code')
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=rtol, atol=atol, err_msg='attention')


class TestTwinVsDense:
    """The jnp twin (train path / non-TPU fallback) against the dense
    encode, over the full structural property space."""

    @pytest.mark.parametrize('token_pad,path_pad', [(0, 0), (1, 2)])
    @pytest.mark.parametrize('data_shards', [1, 2, 4])
    def test_property_regime(self, token_pad, path_pad, data_shards):
        rng = np.random.default_rng(7)
        params = small_params()
        for _trial in range(8):
            contexts = int(rng.choice([3, 5, 8, 13]))
            batch = random_plane_batch(rng, 8, contexts, token_pad,
                                       path_pad)
            packed = packed_lib.pack_batch(batch, token_pad, path_pad,
                                           data_shards=data_shards,
                                           capacity_minimum=4)
            got = ragged(params, packed, contexts, token_pad, path_pad,
                         use_kernel=False)
            assert_encode_close(got, dense_reference(params, batch))

    def test_capacity_rungs_agree(self):
        """The same batch packed at every serving-ladder capacity rung
        must produce identical outputs — capacity padding is inert."""
        rng = np.random.default_rng(3)
        params = small_params()
        batch = random_plane_batch(rng, 8, 6)
        want = dense_reference(params, batch)
        for rung in (4, 16, 64, 256):
            packed = packed_lib.pack_batch(batch, 0, 0,
                                           capacity_minimum=rung)
            assert packed.ctx.shape[1] >= rung
            got = ragged(params, packed, 6, 0, 0, use_kernel=False)
            assert_encode_close(got, want)

    def test_all_padding_batch_matches_dense_uniform(self):
        """count == 0 rows: the dense path produces a FINITE uniform
        attention (1/C) and code = x_pad; the fused fixup must match."""
        contexts = 5
        from code2vec_tpu.data.reader import Batch
        zero = Batch(source=np.zeros((4, contexts), np.int32),
                     path=np.zeros((4, contexts), np.int32),
                     target=np.zeros((4, contexts), np.int32),
                     mask=np.zeros((4, contexts), np.float32),
                     label=np.zeros((4,), np.int32),
                     weight=np.zeros((4,), np.float32))
        params = small_params()
        packed = packed_lib.pack_batch(zero, 0, 0, capacity_minimum=4)
        got = ragged(params, packed, contexts, 0, 0, use_kernel=False)
        assert_encode_close(got, dense_reference(params, zero))
        np.testing.assert_allclose(np.asarray(got[1]),
                                   np.full((4, contexts), 1.0 / contexts))

    def test_capacity_smaller_than_batch(self):
        """More examples than context rows (the sparse-eval regression
        shape from tests/test_packed.py)."""
        from code2vec_tpu.data.reader import Batch, context_valid_mask
        contexts, batch_size = 6, 64
        rng = np.random.default_rng(2)
        batch = random_plane_batch(rng, batch_size, contexts)
        lengths = np.zeros((batch_size,), np.int64)
        lengths[:4] = [1, 2, 0, 3]
        dead = np.arange(contexts)[None, :] >= lengths[:, None]
        source = batch.source.copy(); source[dead] = 0
        path = batch.path.copy(); path[dead] = 0
        target = batch.target.copy(); target[dead] = 0
        mask = context_valid_mask(source, path, target, 0, 0)
        batch = batch._replace(source=source, path=path, target=target,
                               mask=mask)
        params = small_params()
        packed = packed_lib.pack_batch(batch, 0, 0, capacity_minimum=4)
        assert packed.ctx.shape[1] < batch_size
        got = ragged(params, packed, contexts, 0, 0, use_kernel=False)
        assert_encode_close(got, dense_reference(params, batch))

    def test_gradients_match_dense(self):
        """loss_and_aux_packed's backward (the fused TRAIN path) against
        the unpack-then-dense loss, all five parameter gradients."""
        rng = np.random.default_rng(1)
        params = small_params()
        batch = random_plane_batch(rng, 8, 6)
        batch = batch._replace(
            label=np.clip(batch.label, 0, 15).astype(np.int32))
        packed = packed_lib.pack_batch(batch, 0, 0, data_shards=2,
                                       capacity_minimum=4)

        def dense_loss(p):
            return functional.loss_and_aux(
                p, batch.source, batch.path, batch.target, batch.mask,
                batch.label, batch.weight, num_valid_targets=16)[0]

        def ragged_loss(p):
            return functional.loss_and_aux_packed(
                p, jnp.asarray(packed.ctx), jnp.asarray(packed.count),
                jnp.asarray(packed.label), jnp.asarray(packed.weight),
                max_contexts=6, token_pad=0, path_pad=0,
                num_valid_targets=16)[0]

        loss_d, grads_d = jax.value_and_grad(dense_loss)(params)
        loss_r, grads_r = jax.value_and_grad(ragged_loss)(params)
        np.testing.assert_allclose(float(loss_r), float(loss_d),
                                   rtol=1e-5)
        for name, got, want in zip(params._fields,
                                   jax.tree_util.tree_leaves(grads_r),
                                   jax.tree_util.tree_leaves(grads_d)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=1e-6,
                                       err_msg=name)

    def test_dropout_runs_and_is_finite(self):
        """Dropout draws over the PACKED layout (a different seed-keyed
        stream than the dense path — the DROPOUT_PRNG_IMPL precedent),
        so the contract is a finite loss + finite grads, not bit
        parity."""
        params = small_params()
        batch = random_plane_batch(np.random.default_rng(5), 8, 6)
        packed = packed_lib.pack_batch(batch, 0, 0, capacity_minimum=4)

        def loss(p):
            return functional.loss_and_aux_packed(
                p, jnp.asarray(packed.ctx), jnp.asarray(packed.count),
                jnp.asarray(np.clip(packed.label, 0, 15)),
                jnp.asarray(packed.weight),
                max_contexts=6, token_pad=0, path_pad=0,
                num_valid_targets=16,
                dropout_rng=jax.random.PRNGKey(7),
                dropout_keep_rate=0.75)[0]

        value, grads = jax.value_and_grad(loss)(params)
        assert np.isfinite(float(value))
        assert all(np.isfinite(np.asarray(g)).all()
                   for g in jax.tree_util.tree_leaves(grads))

    def test_kernel_dropout_bit_matches_twin_draw(self):
        """Dropout moved INTO the fused pair: the packed-layout keep
        mask is drawn outside the kernel and applied to its embedding
        inputs, so with the same threaded key the kernel forward and
        the jnp twin consume bit-identical inputs — outputs agree to
        fp32 rounding, across prng impls."""
        params = small_params()
        packed = packed_lib.pack_batch(
            random_plane_batch(np.random.default_rng(0), 8, 4), 0, 0,
            capacity_minimum=4)
        for impl in ('threefry2x32', 'rbg'):
            kw = dict(dropout_rng=jax.random.PRNGKey(3),
                      dropout_keep_rate=0.5, dropout_prng_impl=impl)
            twin = ragged(params, packed, 4, 0, 0, use_kernel=False,
                          **kw)
            kern = ragged(params, packed, 4, 0, 0, use_kernel=True,
                          interpret=True, **kw)
            assert_encode_close(kern, twin)


class TestKernelInterpret:
    """The Pallas kernel in interpreter mode — no TPU needed for the
    FuseMax single-pass logic."""

    @pytest.mark.parametrize('data_shards', [1, 2])
    def test_kernel_matches_dense(self, data_shards):
        rng = np.random.default_rng(11)
        params = small_params()
        for _trial in range(6):
            contexts = int(rng.choice([3, 5, 8]))
            batch = random_plane_batch(rng, 8, contexts, 1, 2)
            packed = packed_lib.pack_batch(batch, 1, 2,
                                           data_shards=data_shards,
                                           capacity_minimum=4)
            got = ragged(params, packed, contexts, 1, 2,
                         use_kernel=True, interpret=True)
            assert_encode_close(got, dense_reference(params, batch))

    def test_multi_tile_online_rescale(self, monkeypatch):
        """Force several grid steps (tiny slot tile) so segments SPAN
        tiles and the running (m, z, acc) rescale actually runs, with
        the per-example stream crossing every tile boundary."""
        monkeypatch.setattr(pallas_ragged, 'SLOT_TILE', 8)
        rng = np.random.default_rng(13)
        params = small_params()
        batch = random_plane_batch(rng, 8, 13, hole_rate=0.4)
        packed = packed_lib.pack_batch(batch, 0, 0, capacity_minimum=4)
        assert packed.ctx.shape[1] > 8  # really multi-tile
        got = ragged(params, packed, 13, 0, 0, use_kernel=True,
                     interpret=True)
        assert_encode_close(got, dense_reference(params, batch))

    def test_kernel_shard_mapped_on_mesh(self):
        """The multi-device route: pallas_call is opaque to GSPMD, so
        the kernel must be shard_mapped over the data axis — parity on
        the 8-virtual-device mesh."""
        from code2vec_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.create_mesh()
        shards = mesh.shape['data']
        rng = np.random.default_rng(17)
        params = small_params()
        batch = random_plane_batch(rng, 2 * shards, 5, 1, 2)
        packed = packed_lib.pack_batch(batch, 1, 2, data_shards=shards,
                                       capacity_minimum=4)
        got = ragged(params, packed, 5, 1, 2, use_kernel=True,
                     interpret=True, mesh=mesh)
        assert_encode_close(got, dense_reference(params, batch))

    def test_bf16_compute_smoke(self):
        """bf16 is the production compute dtype: the kernel and twin
        must agree with the dense bf16 path to bf16 resolution."""
        rng = np.random.default_rng(19)
        params = small_params()
        batch = random_plane_batch(rng, 8, 6)
        packed = packed_lib.pack_batch(batch, 0, 0, capacity_minimum=4)
        want = functional.encode(params, batch.source, batch.path,
                                 batch.target, batch.mask,
                                 dtype=jnp.bfloat16)
        for kw in ({'use_kernel': False},
                   {'use_kernel': True, 'interpret': True}):
            got = ragged(params, packed, 6, 0, 0, dtype=jnp.bfloat16,
                         **kw)
            assert_encode_close(got, want, rtol=0.03, atol=0.02)


def _packed_losses(params, packed, contexts, token_pad=0, path_pad=0,
                   **kw):
    """value_and_grad-ready packed loss closure (custom VJP by
    default; kw overrides select the kernel pair / autodiff twin)."""
    def loss(p):
        return functional.loss_and_aux_packed(
            p, jnp.asarray(packed.ctx), jnp.asarray(packed.count),
            jnp.asarray(np.clip(packed.label, 0, 15)),
            jnp.asarray(packed.weight), max_contexts=contexts,
            token_pad=token_pad, path_pad=path_pad,
            num_valid_targets=16, **kw)[0]
    return loss


def _dense_loss(params, batch):
    def loss(p):
        return functional.loss_and_aux(
            p, batch.source, batch.path, batch.target, batch.mask,
            np.clip(batch.label, 0, 15).astype(np.int32), batch.weight,
            num_valid_targets=16)[0]
    return loss


def assert_grads_close(got, want, fields, rtol=2e-4, atol=1e-6):
    for name, a, b in zip(fields, jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol, err_msg=name)


class TestFusedBackward:
    """The custom-VJP recompute backward (ragged_encode_code): gradient
    parity for all five params against the unpack-then-dense loss across
    the packed property regime, the Pallas backward kernel in
    interpreter mode (single-shard, flat multi-shard, multi-tile,
    shard_mapped), dropout-mask bit-match between the fused pair and the
    twin, bf16 smoke, and the no-per-slot-residuals contract."""

    def test_grad_parity_property_regime(self):
        """Holes, pad rows (count == 0), fill rates, shard counts: the
        custom-VJP gradients must match the dense path's for all five
        params (the fp32-rounding regime)."""
        rng = np.random.default_rng(23)
        params = small_params()
        for shards in (1, 2, 4):
            contexts = int(rng.choice([3, 5, 8, 13]))
            batch = random_plane_batch(rng, 8, contexts, hole_rate=0.4,
                                       pad_row_rate=0.3)
            packed = packed_lib.pack_batch(batch, 0, 0,
                                           data_shards=shards,
                                           capacity_minimum=4)
            loss_d, grads_d = jax.value_and_grad(
                _dense_loss(params, batch))(params)
            loss_r, grads_r = jax.value_and_grad(
                _packed_losses(params, packed, contexts))(params)
            np.testing.assert_allclose(float(loss_r), float(loss_d),
                                       rtol=1e-5)
            assert_grads_close(grads_r, grads_d, params._fields)

    def test_grad_parity_capacity_rungs(self):
        """The same batch packed at every serving-ladder rung must
        produce identical gradients — backward capacity padding is as
        inert as forward's."""
        rng = np.random.default_rng(29)
        params = small_params()
        batch = random_plane_batch(rng, 8, 6)
        _, grads_d = jax.value_and_grad(_dense_loss(params,
                                                    batch))(params)
        for rung in (4, 16, 64, 256):
            packed = packed_lib.pack_batch(batch, 0, 0,
                                           capacity_minimum=rung)
            _, grads_r = jax.value_and_grad(
                _packed_losses(params, packed, 6))(params)
            assert_grads_close(grads_r, grads_d, params._fields)

    def test_kernel_backward_matches_dense(self):
        """The Pallas backward kernel (interpreter mode), single-shard
        and flat multi-shard, against the dense gradients."""
        rng = np.random.default_rng(31)
        params = small_params()
        for shards in (1, 2):
            batch = random_plane_batch(rng, 8, 7, 1, 2)
            packed = packed_lib.pack_batch(batch, 1, 2,
                                           data_shards=shards,
                                           capacity_minimum=4)
            _, grads_d = jax.value_and_grad(_dense_loss(params,
                                                        batch))(params)
            _, grads_k = jax.value_and_grad(_packed_losses(
                params, packed, 7, 1, 2,
                use_ragged_kernel=True))(params)
            assert_grads_close(grads_k, grads_d, params._fields)

    def test_kernel_backward_multi_tile(self, monkeypatch):
        """Segments spanning several grid steps: the backward kernel
        reads saved (m, z) — no running rescale — but its per-tile
        accumulation of the dense grads must still sum across tiles."""
        monkeypatch.setattr(pallas_ragged, 'SLOT_TILE', 8)
        rng = np.random.default_rng(37)
        params = small_params()
        batch = random_plane_batch(rng, 8, 13, hole_rate=0.4)
        packed = packed_lib.pack_batch(batch, 0, 0, capacity_minimum=4)
        assert packed.ctx.shape[1] > 8
        _, grads_t = jax.value_and_grad(
            _packed_losses(params, packed, 13))(params)
        _, grads_k = jax.value_and_grad(_packed_losses(
            params, packed, 13, use_ragged_kernel=True))(params)
        assert_grads_close(grads_k, grads_t, params._fields)

    def test_kernel_backward_shard_mapped_on_mesh(self):
        """The multi-device route: forward AND backward kernels
        shard_mapped over the data axis, gradient parity on the
        8-virtual-device mesh."""
        from code2vec_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.create_mesh()
        shards = mesh.shape['data']
        rng = np.random.default_rng(41)
        params = small_params()
        batch = random_plane_batch(rng, 2 * shards, 5, 1, 2)
        packed = packed_lib.pack_batch(batch, 1, 2, data_shards=shards,
                                       capacity_minimum=4)
        _, grads_d = jax.value_and_grad(_dense_loss(params,
                                                    batch))(params)
        _, grads_k = jax.value_and_grad(_packed_losses(
            params, packed, 5, 1, 2, use_ragged_kernel=True,
            ragged_mesh=mesh))(params)
        assert_grads_close(grads_k, grads_d, params._fields)

    @pytest.mark.parametrize('hits', ['all_same', 'all_distinct',
                                      'half_one_row'])
    def test_table_grad_extremes(self, hits):
        """The duplicate-index extremes of the table-gradient
        scatter-adds: every slot of the batch on one row (one giant
        run), no two slots on a row, and half of them on one row. The
        token- and path-table gradients of the custom VJP must equal
        autodiff of the plane path."""
        from code2vec_tpu.data.reader import Batch
        rows, contexts = 4, 3
        slots = rows * contexts
        distinct = np.arange(1, 1 + slots, dtype=np.int32)
        if hits == 'all_same':
            source, path, target = (np.full(slots, row, np.int32)
                                    for row in (7, 5, 7))
        else:
            source, path, target = distinct, distinct, distinct + slots
            if hits == 'half_one_row':
                source, path, target = (
                    np.where(np.arange(slots) % 2 == 0, row, a)
                    for row, a in ((31, source), (15, path), (31, target)))
        batch = Batch(
            source=source.reshape(rows, contexts),
            path=path.reshape(rows, contexts),
            target=target.reshape(rows, contexts),
            mask=np.ones((rows, contexts), np.float32),
            label=np.arange(1, 1 + rows, dtype=np.int32),
            weight=np.ones((rows,), np.float32))
        params = small_params(path_dim=8)
        packed = packed_lib.pack_batch(batch, 0, 0, capacity_minimum=4)
        grads_d = jax.grad(_dense_loss(params, batch))(params)
        grads_r = jax.grad(_packed_losses(params, packed, contexts))(params)
        for name in ('token_embedding', 'path_embedding'):
            got = np.asarray(getattr(grads_r, name))
            want = np.asarray(getattr(grads_d, name))
            assert np.abs(want).sum() > 0
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6,
                                       err_msg=name)

    def test_dropout_bit_match_fused_vs_twin(self):
        """One threaded key, three consumers — the autodiff twin, the
        custom-VJP twin pair, the custom-VJP kernel pair — must all
        draw the SAME packed-layout mask: identical losses to fp32
        rounding and matching gradients (the recompute backward
        re-draws the mask rather than storing it)."""
        rng = np.random.default_rng(43)
        params = small_params()
        packed = packed_lib.pack_batch(
            random_plane_batch(rng, 8, 6), 0, 0, data_shards=2,
            capacity_minimum=4)
        for impl in ('threefry2x32', 'rbg'):
            kw = dict(dropout_rng=jax.random.PRNGKey(11),
                      dropout_keep_rate=0.75, dropout_prng_impl=impl)
            loss_a, grads_a = jax.value_and_grad(_packed_losses(
                params, packed, 6, ragged_custom_vjp=False,
                use_ragged_kernel=False, **kw))(params)
            loss_v, grads_v = jax.value_and_grad(_packed_losses(
                params, packed, 6, **kw))(params)
            loss_k, grads_k = jax.value_and_grad(_packed_losses(
                params, packed, 6, use_ragged_kernel=True,
                **kw))(params)
            np.testing.assert_allclose(float(loss_v), float(loss_a),
                                       rtol=1e-6)
            np.testing.assert_allclose(float(loss_k), float(loss_a),
                                       rtol=1e-6)
            assert_grads_close(grads_v, grads_a, params._fields)
            assert_grads_close(grads_k, grads_a, params._fields)

    def test_bf16_backward_smoke(self):
        """bf16 compute: the custom-VJP gradients track the autodiff
        twin's to bf16 resolution."""
        rng = np.random.default_rng(47)
        params = small_params()
        packed = packed_lib.pack_batch(
            random_plane_batch(rng, 8, 6), 0, 0, capacity_minimum=4)
        _, grads_a = jax.value_and_grad(_packed_losses(
            params, packed, 6, dtype=jnp.bfloat16,
            ragged_custom_vjp=False))(params)
        _, grads_v = jax.value_and_grad(_packed_losses(
            params, packed, 6, dtype=jnp.bfloat16))(params)
        assert_grads_close(grads_v, grads_a, params._fields,
                           rtol=0.05, atol=0.02)

    def test_count_zero_rows_route_through_x_pad(self):
        """count == 0 rows take code = x_pad = tanh(pad_ctx @ W): a
        NONZERO cotangent on their code vectors (sum-of-code, unlike
        the weight-masked loss) must flow through that expression
        exactly as the autodiff twin's does."""
        contexts = 5
        from code2vec_tpu.data.reader import Batch
        zero = Batch(source=np.ones((4, contexts), np.int32),
                     path=np.ones((4, contexts), np.int32),
                     target=np.ones((4, contexts), np.int32),
                     mask=np.zeros((4, contexts), np.float32),
                     label=np.zeros((4,), np.int32),
                     weight=np.zeros((4,), np.float32))
        zero = zero._replace(source=np.zeros_like(zero.source),
                             path=np.zeros_like(zero.path),
                             target=np.zeros_like(zero.target))
        params = small_params()
        packed = packed_lib.pack_batch(zero, 0, 0, capacity_minimum=4)

        def code_sum(p, custom_vjp):
            return pallas_ragged.ragged_encode_code(
                p.token_embedding, p.path_embedding, p.transform,
                p.attention, jnp.asarray(packed.ctx),
                jnp.asarray(packed.count), token_pad=0, path_pad=0,
                use_kernel=False, custom_vjp=custom_vjp).sum()

        grads_a = jax.grad(lambda p: code_sum(p, False))(params)
        grads_v = jax.grad(lambda p: code_sum(p, True))(params)
        # encoder params only: target_embedding is out of scope here
        for name in ('token_embedding', 'path_embedding', 'transform',
                     'attention'):
            np.testing.assert_allclose(
                np.asarray(getattr(grads_v, name)),
                np.asarray(getattr(grads_a, name)),
                rtol=2e-4, atol=1e-6, err_msg=name)
        assert float(jnp.abs(grads_v.transform).sum()) > 0.0

    def test_custom_vjp_saves_no_per_slot_residuals(self):
        """THE residual contract (acceptance): the vjp closure of the
        custom-VJP packed loss holds NO floating residual of per-slot
        rank — the (D, cap, 3d) gathered embeddings, the dropout masks
        and the (D, cap, D) activations are recomputed, not stored —
        while the autodiff twin's closure demonstrably stores them
        (the check would catch a silent regression to storing)."""
        rng = np.random.default_rng(53)
        params = small_params()
        packed = packed_lib.pack_batch(
            random_plane_batch(rng, 8, 6), 0, 0, data_shards=2,
            capacity_minimum=4)
        kw = dict(dropout_rng=jax.random.PRNGKey(5),
                  dropout_keep_rate=0.75)

        def residual_shapes(ragged_custom_vjp):
            # floating rank-3+ residuals = the per-slot tensors ((D,
            # cap, d) embeddings, (D, cap, Dc) activations); the int32
            # ctx wire and tiny CE-tail leaves are inputs/bookkeeping
            loss = _packed_losses(params, packed, 6,
                                  ragged_custom_vjp=ragged_custom_vjp,
                                  **kw)
            _, f_vjp = jax.vjp(loss, params)
            return [tuple(leaf.shape)
                    for leaf in jax.tree_util.tree_leaves(f_vjp)
                    if hasattr(leaf, 'ndim') and leaf.ndim >= 3
                    and jnp.issubdtype(leaf.dtype, jnp.floating)]

        assert residual_shapes(True) == []
        assert len(residual_shapes(False)) > 0


@pytest.fixture(scope='module')
def trainer_pair():
    """One (plain, fused) trainer pair shared by the integration tests:
    Trainer construction compiles the full step-program family on the
    8-device mesh, so rebuilding per test would dominate the file's
    tier-1 budget. Dropout off: the two layouts draw different masks.
    The fused trainer deliberately relies on the config DEFAULT (ON
    since the custom-VJP backward landed); the plain arm pins the
    unpack path."""
    plain = make_trainer(DROPOUT_KEEP_RATE=1.0,
                         USE_PALLAS_RAGGED_FUSION=False)
    fused = make_trainer(DROPOUT_KEEP_RATE=1.0)
    return plain, fused


class TestTrainerIntegration:
    """USE_PALLAS_RAGGED_FUSION threaded through the packed train/eval/
    predict steps: fused vs unpack-then-dense on the 8-virtual-device
    mesh (CPU, so the twin runs — the same code the TPU train path
    uses)."""

    def _packed(self, trainer, n=3):
        rng = np.random.default_rng(5)
        shards = trainer.mesh.shape['data']
        out = []
        for _ in range(n):
            batch = random_plane_batch(rng, 8, 4, pad_row_rate=0.1)
            batch = batch._replace(
                label=np.clip(batch.label, 0, 15).astype(np.int32))
            out.append(packed_lib.pack_batch(batch, 0, 0,
                                             data_shards=shards,
                                             capacity_minimum=4))
        return out

    def test_train_steps_match(self, trainer_pair):
        plain, fused = trainer_pair
        packed = self._packed(plain)
        state_a = plain.init_state(seed=0)
        state_b = fused.init_state(seed=0)
        for pb in packed:
            state_a, loss_a = plain.train_step(state_a, pb)
            state_b, loss_b = fused.train_step(state_b, pb)
            np.testing.assert_allclose(float(loss_b), float(loss_a),
                                       rtol=1e-5)
        for leaf_a, leaf_b in zip(
                jax.tree_util.tree_leaves(state_a.params),
                jax.tree_util.tree_leaves(state_b.params)):
            np.testing.assert_allclose(np.asarray(leaf_b),
                                       np.asarray(leaf_a),
                                       rtol=2e-4, atol=1e-6)

    def test_eval_and_all_predict_tiers_match(self, trainer_pair):
        plain, fused = trainer_pair
        packed = self._packed(plain, n=1)
        params = plain.init_state(seed=1).params
        out_a = plain.eval_step(params, packed[0])
        out_b = fused.eval_step(params, packed[0])
        np.testing.assert_array_equal(np.asarray(out_a['topk_indices']),
                                      np.asarray(out_b['topk_indices']))
        np.testing.assert_allclose(float(out_b['loss_sum']),
                                   float(out_a['loss_sum']), rtol=1e-5)
        assert float(out_a['weight_sum']) == float(out_b['weight_sum'])
        from code2vec_tpu.training.trainer import PREDICT_TIERS
        for tier in PREDICT_TIERS:
            pa = plain.predict_step(params, packed[0], tier=tier)
            pb = fused.predict_step(params, packed[0], tier=tier)
            assert set(pa) == set(pb), tier
            for key in pa:
                np.testing.assert_allclose(
                    np.asarray(pb[key]).astype(np.float64),
                    np.asarray(pa[key]).astype(np.float64),
                    rtol=1e-5, atol=1e-6, err_msg='%s/%s' % (tier, key))

    def test_zero_postwarm_compiles(self, trainer_pair):
        """The fused packed programs must be as shape-stable as the
        unpack path: repeated dispatches on warm (bucket, capacity,
        tier) shapes add NOTHING to the compile counter — the serving
        ladder's steady-state contract. (Predict is deterministic, so
        the shared dropout-off trainer is exactly the serving shape.)"""
        from code2vec_tpu.parallel import mesh as mesh_lib
        from code2vec_tpu.telemetry import core
        from code2vec_tpu.telemetry.jit_tracker import \
            install_compile_listener
        from code2vec_tpu.training.trainer import PREDICT_TIERS
        fused = trainer_pair[1]
        packed = self._packed(fused, n=2)
        params = fused.init_state(seed=0).params
        placed = [mesh_lib.shard_batch(pb.device_arrays(), fused.mesh,
                                       False) for pb in packed]
        assert placed[0][0].shape == placed[1][0].shape  # same capacity
        core.reset()
        core.enable()
        try:
            assert install_compile_listener()
            compiles = core.registry().counter('jit/compiles_total')
            for tier in PREDICT_TIERS:  # warm every fused program
                fused.predict_step_placed(params, placed[0], tier=tier)
            warm = compiles.value
            for tier in PREDICT_TIERS:
                for arrays in placed:
                    out = fused.predict_step_placed(params, arrays,
                                                    tier=tier)
                    jax.block_until_ready(out)
            assert compiles.value - warm == 0, (
                '%d XLA compiles after warmup on fixed packed shapes'
                % (compiles.value - warm))
        finally:
            core.disable()
            core.reset()
        # the ledger's executables bucket stays complete: the AOT
        # memory_analysis the serving warmup records per (bucket x
        # capacity x tier) must measure the FUSED program too
        info = fused.predict_program_memory(params, placed[0],
                                            tier='attention')
        assert info is not None and set(info) == {
            'generated_code_bytes', 'temp_bytes', 'argument_bytes',
            'output_bytes'}

    def test_zero_postwarm_compiles_fused_train(self, trainer_pair):
        """The custom-VJP train step is as shape-stable as the rest:
        repeated train dispatches on a warm (shards, capacity) shape add
        NOTHING to the compile counter — the recompute backward, the
        dropout re-draw and the table scatter-adds all key on the same
        packed shapes."""
        from code2vec_tpu.telemetry import core
        from code2vec_tpu.telemetry.jit_tracker import \
            install_compile_listener
        fused = trainer_pair[1]
        packed = self._packed(fused, n=3)
        assert packed[0].ctx.shape == packed[1].ctx.shape
        state = fused.init_state(seed=0)
        core.reset()
        core.enable()
        try:
            assert install_compile_listener()
            compiles = core.registry().counter('jit/compiles_total')
            state, _ = fused.train_step(state, packed[0])  # warm
            warm = compiles.value
            for pb in packed:
                state, loss = fused.train_step(state, pb)
                jax.block_until_ready(loss)
            assert compiles.value - warm == 0, (
                '%d XLA compiles after warmup on the fused train step'
                % (compiles.value - warm))
        finally:
            core.disable()
            core.reset()
        # the bench A/B's memory axis: the train program's AOT analysis
        # must resolve on this backend too (temp_bytes is the residual
        # claim's measurable)
        from code2vec_tpu.parallel import mesh as mesh_lib
        placed = mesh_lib.shard_batch(packed[0].device_arrays(),
                                      fused.mesh, False)
        info = fused.train_program_memory(state, placed)
        assert info is not None and 'temp_bytes' in info
