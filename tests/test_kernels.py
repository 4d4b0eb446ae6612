"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes, dtypes, dilations and channel widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import meshnet
from repro.core.meshnet import MeshNetConfig
from repro.kernels import dice as dice_kernel
from repro.kernels import dilated_conv3d as conv_kernel
from repro.kernels import megakernel, ops, ref
from repro.training import losses

KEY = jax.random.PRNGKey(7)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class TestDilatedConv3D:
    @pytest.mark.parametrize("dilation", [1, 2, 4, 8, 16])
    def test_dilation_sweep(self, dilation):
        x = _rand(KEY, (1, 32, 32, 32, 5), jnp.float32)
        w = _rand(jax.random.PRNGKey(1), (3, 3, 3, 5, 5), jnp.float32) * 0.2
        b = _rand(jax.random.PRNGKey(2), (5,), jnp.float32) * 0.1
        out = conv_kernel.dilated_conv3d(x, w, b, dilation=dilation, interpret=True)
        expect = ref.dilated_conv3d(x, w, b, dilation=dilation)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=5e-5)

    @pytest.mark.parametrize(
        "cin,cout", [(1, 5), (5, 5), (5, 3), (21, 21), (10, 50)]
    )
    def test_channel_sweep(self, cin, cout):
        x = _rand(KEY, (1, 16, 16, 16, cin), jnp.float32)
        w = _rand(jax.random.PRNGKey(1), (3, 3, 3, cin, cout), jnp.float32) * 0.1
        b = jnp.zeros((cout,))
        out = conv_kernel.dilated_conv3d(x, w, b, dilation=2, interpret=True)
        expect = ref.dilated_conv3d(x, w, b, dilation=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=5e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep(self, dtype):
        x = _rand(KEY, (1, 16, 16, 16, 5), dtype)
        w = _rand(jax.random.PRNGKey(1), (3, 3, 3, 5, 5), dtype) * 0.2
        b = jnp.zeros((5,), dtype)
        out = conv_kernel.dilated_conv3d(x, w, b, dilation=4, interpret=True)
        expect = ref.dilated_conv3d(x, w, b, dilation=4)
        tol = 5e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol
        )

    def test_batched(self):
        x = _rand(KEY, (3, 16, 16, 16, 5), jnp.float32)
        w = _rand(jax.random.PRNGKey(1), (3, 3, 3, 5, 5), jnp.float32) * 0.2
        b = jnp.zeros((5,))
        out = conv_kernel.dilated_conv3d(x, w, b, dilation=2, interpret=True)
        expect = ref.dilated_conv3d(x, w, b, dilation=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=5e-5)

    def test_fused_affine_relu_epilogue(self):
        x = _rand(KEY, (1, 16, 16, 16, 5), jnp.float32)
        w = _rand(jax.random.PRNGKey(1), (3, 3, 3, 5, 5), jnp.float32) * 0.2
        b = jnp.zeros((5,))
        s = jnp.asarray([1.5, 0.5, 2.0, 1.0, 0.1])
        o = jnp.asarray([0.1, -0.2, 0.0, 0.3, -0.1])
        out = conv_kernel.dilated_conv3d(
            x, w, b, dilation=8, scale=s, offset=o, fuse_affine=True, interpret=True
        )
        expect = ref.dilated_conv3d(x, w, b, dilation=8, scale=s, offset=o, fuse_affine=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=5e-5)
        assert float(out.min()) >= 0.0  # ReLU applied

    def test_odd_shapes_via_ops_wrapper(self):
        x = _rand(KEY, (1, 24, 20, 28, 5), jnp.float32)
        w = _rand(jax.random.PRNGKey(1), (3, 3, 3, 5, 5), jnp.float32) * 0.2
        b = jnp.zeros((5,))
        out = ops.dilated_conv3d(x, w, b, dilation=4, interpret=True)
        expect = ref.dilated_conv3d(x, w, b, dilation=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=5e-5)

    def test_kernel_backed_meshnet_matches_reference_model(self):
        cfg = MeshNetConfig()
        p = meshnet.init(KEY, cfg)
        x = _rand(jax.random.PRNGKey(3), (1, 20, 24, 16), jnp.float32)
        out = ops.meshnet_apply(p, x, cfg, interpret=True)
        expect = meshnet.apply(p, x, cfg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-4)

    def test_vmem_budget(self):
        # Every paper width and dilation has a one-layer plan (the tile
        # the kernel runs) whose working set, priced at its padded
        # plane-layout size on the conformed 256-wide volume, fits the
        # planner's budget. The budget plus the compiler headroom stays
        # inside a v5e core's 128 MiB of VMEM.
        assert conv_kernel.VMEM_BUDGET + megakernel.VMEM_HEADROOM <= 128 * 2**20
        for c in (5, 21):
            for d in (1, 2, 4, 8, 16):
                seg = megakernel.plan((d,), c, c, 0, (256, 256, 256)).segments[0]
                assert not seg.fuse_head and seg.cout == c
                assert megakernel._segment_vmem_bytes(seg) <= conv_kernel.VMEM_BUDGET
        assert conv_kernel.vmem_bytes(16, 5, 5) < conv_kernel.VMEM_BUDGET

    @pytest.mark.parametrize("dilation", [1, 4, 16])
    @pytest.mark.parametrize("fuse", [False, True])
    def test_output_independent_of_tile(self, dilation, fuse):
        # The (Z, Y) tile only changes which windows are DMA'd and how the
        # rows are blocked, never a voxel's taps: block 8 and block 16
        # must agree to f32 rounding, and both match the oracle.
        x = _rand(KEY, (2, 16, 16, 16, 5), jnp.float32)
        w = _rand(jax.random.PRNGKey(1), (3, 3, 3, 5, 5), jnp.float32) * 0.2
        b = _rand(jax.random.PRNGKey(2), (5,), jnp.float32) * 0.1
        kw = dict(dilation=dilation, interpret=True, fuse_affine=fuse)
        if fuse:
            kw.update(scale=jnp.asarray([1.5, 0.5, 2.0, 1.0, 0.1]),
                      offset=jnp.asarray([0.1, -0.2, 0.0, 0.3, -0.1]))
        small = conv_kernel.dilated_conv3d(x, w, b, block=8, **kw)
        big = conv_kernel.dilated_conv3d(x, w, b, block=16, **kw)
        np.testing.assert_allclose(np.asarray(small), np.asarray(big), atol=1e-6)
        expect = ref.dilated_conv3d(
            x, w, b, dilation=dilation, fuse_affine=fuse,
            scale=kw.get("scale"), offset=kw.get("offset"),
        )
        np.testing.assert_allclose(np.asarray(big), np.asarray(expect), atol=5e-5)

    def test_vmem_bytes_counts_padded_rows_and_lanes(self):
        # Channels occupy whole 8-row sublane groups and a row occupies
        # whole 128-lane tiles: the input window alone of a block-16,
        # d=16 step on a 256-wide volume is (16+32)^2 rows of 256 lanes
        # by 8 channel rows — and the working set grows with the dilation.
        window = (16 + 32) ** 2 * 256 * 8 * 4
        assert conv_kernel.vmem_bytes(16, 5, 5, dilation=16) >= window
        assert conv_kernel.vmem_bytes(16, 5, 5, dilation=1) < \
            conv_kernel.vmem_bytes(16, 5, 5, dilation=16)
        # a 200-wide volume pads its rows to 256 lanes: same bytes
        assert conv_kernel.vmem_bytes(16, 5, 5, width=200) == \
            conv_kernel.vmem_bytes(16, 5, 5, width=256)

    def test_vmem_guard_raises_with_suggested_block(self):
        # An explicit block over the budget is refused at the kernel
        # entrypoint, pre-pallas_call, and the refusal names the tile the
        # planner would run instead — one that fits.
        x = _rand(KEY, (1, 64, 64, 64, 21), jnp.float32)
        w = _rand(jax.random.PRNGKey(1), (3, 3, 3, 21, 21), jnp.float32)
        with pytest.raises(ValueError, match=r"VMEM.*planner's tile is \(\d+, \d+\)"):
            conv_kernel.dilated_conv3d(
                x, w, jnp.zeros((21,)), dilation=8, block=64, interpret=True
            )
        assert conv_kernel.vmem_bytes(64, 21, 21, dilation=8, width=64) > \
            conv_kernel.VMEM_BUDGET
        seg = megakernel.plan((8,), 21, 21, 0, (64, 64, 64)).segments[0]
        assert megakernel._segment_vmem_bytes(seg, width=64) <= conv_kernel.VMEM_BUDGET


class TestCompiledOnly:
    """Interpretation is asked for, never fallen into."""

    def test_kernel_without_interpret_raises_on_cpu(self):
        # the kernels default to compiled Mosaic; on CPU arrays that must
        # fail loudly rather than silently run the interpreter
        x = _rand(KEY, (1, 8, 8, 8, 5), jnp.float32)
        w = _rand(jax.random.PRNGKey(1), (3, 3, 3, 5, 5), jnp.float32)
        with pytest.raises(Exception, match="(?i)interpret|tpu|mosaic|cpu"):
            conv_kernel.dilated_conv3d(x, w, jnp.zeros((5,)), dilation=1)

    def test_resolve_interpret_is_decided_per_call(self, monkeypatch):
        assert ops.resolve_interpret(True) is True
        assert ops.resolve_interpret(False) is False
        assert ops.resolve_interpret(None) is (jax.default_backend() != "tpu")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert ops.resolve_interpret(None) is False
        with pytest.raises(ValueError, match="interpret"):
            ops.resolve_interpret(True)

    def test_plane_layout_roundtrip(self):
        # (B, D, H, W, C) -> (B, Z, rows(C), Y * lanes(W)) and back, with
        # the Z/Y padding in place and channel/lane padding zero
        x = _rand(KEY, (2, 5, 6, 7, 3), jnp.float32)
        a = megakernel.to_planes(x, (1, 2), (3, 0))
        assert a.shape == (2, 8, 8, 9 * 128)
        planes = a.reshape(2, 8, 8, 9, 128)
        assert float(jnp.abs(planes[:, :, 3:]).max()) == 0.0
        assert float(jnp.abs(planes[..., 7:]).max()) == 0.0
        back = megakernel.from_planes(a[:, 1:, :, 3 * 128:], (5, 6, 7, 3))
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


class TestDecodeAttentionKernel:
    @pytest.mark.parametrize(
        "B,H,KV,hd,S,pos,blk",
        [
            (2, 8, 2, 32, 100, 57, 32),  # GQA 4x, ragged S, mid pos
            (1, 4, 4, 16, 64, 63, 64),  # MHA, single block, full cache
            (3, 16, 8, 64, 200, 10, 48),  # mostly-masked cache
            (1, 8, 1, 32, 96, 95, 32),  # MQA
        ],
    )
    def test_matches_oracle(self, B, H, KV, hd, S, pos, blk):
        from repro.kernels.decode_attention import decode_attention

        q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, H, hd))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd))
        out = decode_attention(q, k, v, jnp.asarray(pos, jnp.int32), block_s=blk)
        expect = ref.decode_attention(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)

    def test_bf16(self):
        from repro.kernels.decode_attention import decode_attention

        mk = lambda key, shape: jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
        q = mk(jax.random.PRNGKey(0), (2, 1, 8, 32))
        k = mk(jax.random.PRNGKey(1), (2, 80, 4, 32))
        v = mk(jax.random.PRNGKey(2), (2, 80, 4, 32))
        out = decode_attention(q, k, v, jnp.asarray(40, jnp.int32), block_s=32)
        expect = ref.decode_attention(q, k, v, 40)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=3e-2
        )


class TestDiceKernel:
    @pytest.mark.parametrize("shape", [(8, 8, 8), (31, 33, 17), (5, 7, 11)])
    @pytest.mark.parametrize("classes", [2, 3, 5])
    def test_counts_match_oracle(self, shape, classes):
        pred = jax.random.randint(KEY, shape, 0, classes)
        truth = jax.random.randint(jax.random.PRNGKey(1), shape, 0, classes)
        counts = dice_kernel.dice_counts(pred, truth, classes, block_rows=8, interpret=True)
        expect = ref.dice_counts(pred, truth, classes)
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(expect))

    def test_dice_score_matches_losses(self):
        pred = jax.random.randint(KEY, (16, 16, 16), 0, 3)
        truth = jax.random.randint(jax.random.PRNGKey(1), (16, 16, 16), 0, 3)
        a = float(ops.dice(pred, truth, 3, interpret=True))
        b = float(losses.dice_score(pred, truth, 3))
        assert abs(a - b) < 1e-6

    def test_perfect_overlap(self):
        x = jax.random.randint(KEY, (12, 12, 12), 0, 4)
        assert float(ops.dice(x, x, 4, interpret=True)) == 1.0
