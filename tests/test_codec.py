"""Wire codec: pack/unpack round-trip for every live-runtime message kind,
exact ``payload_bytes`` on packed buffers, and the codec-enabled transport
(including a full live training run proving the protocol is
serialization-clean).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import codec
from repro.runtime.transport import Transport, payload_bytes


def _assert_round_trip_equal(a, b):
    assert type(b) is type(a) or (
        hasattr(a, "shape") and isinstance(b, np.ndarray))
    if isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            _assert_round_trip_equal(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _assert_round_trip_equal(x, y)
    elif hasattr(a, "shape") and hasattr(a, "dtype"):
        assert np.asarray(a).dtype == b.dtype and np.asarray(a).shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), b)
    else:
        assert a == b


# every message kind the live runtime puts on the transport, with
# representative payloads (runtime/live.py + runtime/transport.py)
MESSAGES = [
    ("act", (3, 7, jnp.ones((16, 8), jnp.float32))),
    ("grad", (3, 7, jnp.full((16, 8), -0.5, jnp.float32))),
    ("loss", (12, 1.375)),
    ("commit", 11),
    ("hb", {"t": 123.25}),
    ("segment", {"stage": 1, "n": 3, "b0": 10, "nb": 5,
                 "stage_devs": [0, 1, 2], "seg_id": 4}),
    ("seg_done", {"stage": 1, "nb": 5, "busy_s": 0.25, "wait_s": 0.125,
                  "host_s": 0.0625, "batch_times": [0.01, 0.02],
                  "seg_id": 4, "ops_done": 10, "aborted": False,
                  "shipped_gen": -1, "stash_high_water": 4}),
    ("replicate", {"batch": 10, "chain": True, "global": False, "stage": 1,
                   "chain_to": 2}),
    ("replicated", {"stage": 1}),
    ("chain_put", {"batch": 10,
                   "layers": {3: jnp.arange(12.0, dtype=jnp.float32),
                              4: jnp.zeros(7, jnp.float32)}}),
    ("global_put", {"batch": 10,
                    "layers": {0: jnp.ones(5, jnp.float32)}}),
    ("fetch_req", {"req_id": 2, "layers": [3, 4], "reply_to": 1}),
    ("fetch_res", {"req_id": 2,
                   "layers": {3: jnp.arange(12.0, dtype=jnp.float32)}}),
    ("repart", {"stage": 0, "n": 2, "range": (0, 3), "stage_devs": [0, 2],
                "need": {1: [2, 3]}, "local": [0, 1], "version": 9}),
    ("recover", {"stage": 1, "n": 2, "range": (4, 7), "stage_devs": [0, 2],
                 "need": {0: [4]}, "local": [5, 6, 7], "version": 9}),
    ("ready", {"stage": 1, "missing": [], "version": 9}),
    ("probe", {}),
    ("probe_ack", {"status": "ok"}),
    ("stop", {}),
]


@pytest.mark.parametrize("kind,payload",
                         MESSAGES, ids=[k for k, _ in MESSAGES])
def test_round_trip_every_message_kind(kind, payload):
    data = codec.encode(kind, payload)
    assert isinstance(data, bytes)
    k2, p2 = codec.decode(data)
    assert k2 == kind
    _assert_round_trip_equal(payload, p2)


def test_scalar_and_numpy_edge_cases():
    payload = {"i": np.int64(5), "f": np.float64(0.5), "b": np.bool_(True),
               "none": None, "neg": -(2 ** 40), "s": "päyload",
               "bytes": b"\x00\xff", "arr0d": np.float32(2.5),
               "ints": np.arange(4, dtype=np.int32)}
    _, p2 = codec.decode(codec.encode("x", payload))
    assert p2["i"] == 5 and isinstance(p2["i"], int)
    assert p2["f"] == 0.5 and isinstance(p2["f"], float)
    assert p2["b"] is True
    assert p2["none"] is None and p2["neg"] == -(2 ** 40)
    assert p2["s"] == "päyload" and p2["bytes"] == b"\x00\xff"
    assert float(p2["arr0d"]) == 2.5
    np.testing.assert_array_equal(p2["ints"], np.arange(4, dtype=np.int32))


def test_tuple_vs_list_preserved():
    _, p2 = codec.decode(codec.encode("x", ((1, 2), [3, 4])))
    assert isinstance(p2, tuple) and isinstance(p2[0], tuple) \
        and isinstance(p2[1], list)


def test_framing_errors_raise():
    data = codec.encode("x", {"a": 1})
    with pytest.raises(ValueError):
        codec.decode(b"JUNK" + data[4:])
    with pytest.raises(ValueError):
        codec.decode(data + b"\x00")
    with pytest.raises(TypeError):
        codec.encode("x", object())
    with pytest.raises(ValueError):
        codec.encode("x", {"a": 1}, tier="gzip")


# ===================== compressed tiers (codec v2) ========================

def _rand(shape, seed=0):
    return np.random.default_rng(seed).standard_normal(shape) \
        .astype(np.float32)


def test_version_stamped_by_actual_compression():
    """A frame is v2 exactly when it CONTAINS compressed tags; frames
    without any are byte-identical to codec v1, so a v1-only decoder
    keeps understanding every uncompressed message from a v2 sender —
    including tier-on frames where every tensor fell back."""
    x = _rand((4, 3))
    assert codec.encode("act", (1, 2, x))[4] == 1           # tier off
    assert codec.encode("act", (1, 2, x), tier="int8")[4] == 2
    nan = np.full((4,), np.nan, np.float32)
    assert codec.encode("act", nan, tier="int8")[4] == 1    # all fell back
    assert codec.encode("hb", {"t": 1.0}, tier="int8")[4] == 1


def test_decoder_accepts_v1_frames():
    """Tags are additive in v2: a hand-stamped v1 frame must keep
    decoding — mixed-version clusters interoperate."""
    data = bytearray(codec.encode("act", (1, 2, _rand((4, 3)))))
    data[4] = 1
    kind, payload = codec.decode(bytes(data))
    assert kind == "act"
    np.testing.assert_array_equal(payload[2], _rand((4, 3)))


@pytest.mark.parametrize("tier", ["fp16", "int8"])
def test_compressed_round_trip_shapes_and_dtype(tier):
    for shape in [(16, 8), (7,), (2, 3, 4)]:
        x = _rand(shape, seed=3)
        data = codec.encode("act", (0, 1, x), tier=tier)
        assert len(data) < len(codec.encode("act", (0, 1, x)))
        _, p = codec.decode(data)
        assert p[2].dtype == np.float32 and p[2].shape == shape


def test_fp16_round_trip_error_is_half_precision():
    x = _rand((64,), seed=4)
    _, y = codec.decode(codec.encode("x", x, tier="fp16"))
    np.testing.assert_array_equal(y, x.astype(np.float16)
                                  .astype(np.float32))


def test_int8_round_trip_error_bound():
    """Per-tensor affine quantization: |x - dq(q(x))| <= scale / 2 with
    scale = (max - min) / 255 (plus f32 rounding slack)."""
    x = _rand((32, 16), seed=5) * 7.0
    _, y = codec.decode(codec.encode("x", x, tier="int8"))
    scale = (float(x.max()) - float(x.min())) / 255.0
    assert np.abs(y - x).max() <= scale * 0.5 * (1 + 1e-5) + 1e-7


def test_zero_length_slice_falls_back_exact():
    x = np.zeros((0,), np.float32)
    for tier in ("fp16", "int8"):
        _, y = codec.decode(codec.encode("x", x, tier=tier))
        assert y.dtype == np.float32 and y.shape == (0,)


def test_nonfinite_tensors_force_f32_fallback():
    x = _rand((8,), seed=6)
    for bad in (np.nan, np.inf, -np.inf):
        z = x.copy()
        z[3] = bad
        for tier in ("fp16", "int8"):
            data = codec.encode("x", z, tier=tier)
            assert len(data) == len(codec.encode("x", z))   # exact tag
            _, y = codec.decode(data)
            np.testing.assert_array_equal(y, z)


def test_degenerate_range_and_overflow_fall_back():
    const = np.full((10,), 2.5, np.float32)          # max == min
    data = codec.encode("x", const, tier="int8")
    assert len(data) == len(codec.encode("x", const))
    np.testing.assert_array_equal(codec.decode(data)[1], const)
    big = np.array([1e38, -1e38], np.float32)        # fp16 overflow
    data = codec.encode("x", big, tier="fp16")
    np.testing.assert_array_equal(codec.decode(data)[1], big)


def test_subnormal_range_falls_back_exact():
    """A subnormal range passes max > min in f64 but underflows the
    STORED f32 scale to 0 — must fall back, not ship scale=0 garbage."""
    x = np.array([0.0, 5e-44, 1e-43], np.float32)    # (max-min)/255 -> 0.0f
    with np.errstate(all="raise"):                   # no div-by-zero either
        data = codec.encode("x", x, tier="int8")
    assert data[4] == 1                              # no compressed tag
    np.testing.assert_array_equal(codec.decode(data)[1], x)


def test_non_f32_tensors_never_compressed():
    for arr in (np.arange(6, dtype=np.int32),
                np.arange(6, dtype=np.float64)):
        data = codec.encode("x", arr, tier="int8")
        _, y = codec.decode(data)
        assert y.dtype == arr.dtype
        np.testing.assert_array_equal(y, arr)


def test_compressed_wire_size_exact():
    """The compressed encodings have a computable exact wire size —
    what `Transport.stats["bytes"]` records under a compressing policy."""
    shape = (16, 8)
    n = 16 * 8
    x = _rand(shape, seed=7)
    header = len(codec.MAGIC) + 1 + 2 + len(b"x")       # magic|ver|kindlen|kind
    assert len(codec.encode("x", x, tier="int8")) \
        == header + 1 + 1 + 4 * len(shape) + 8 + n      # tag|ndim|dims|lo,scale|q
    assert len(codec.encode("x", x, tier="fp16")) \
        == header + 1 + 1 + 4 * len(shape) + 2 * n      # tag|ndim|dims|f16
    assert len(codec.encode("x", x)) \
        == header + 1 + 1 + len(b"float32") + 1 + 4 * len(shape) + 4 * n


def test_wire_policy_classes():
    pol = codec.WirePolicy(data="int8", replica="fp16")
    assert pol.tier_for("act") == "int8" and pol.tier_for("grad") == "int8"
    assert pol.tier_for("chain_put") == "fp16" \
        and pol.tier_for("global_put") == "fp16"
    # §III-F redistribution and control traffic stay exact, always
    for kind in ("fetch_res", "install", "segment", "hello", "hb"):
        assert pol.tier_for(kind) == "off"
    assert pol.any_compression()
    assert not codec.WirePolicy().any_compression()
    assert codec.WirePolicy.from_payload(pol.to_payload()) == pol
    with pytest.raises(ValueError):
        codec.WirePolicy(data="int4")


def test_payload_bytes_exact_on_packed_buffers():
    """A packed flat weight slice has an exact wire size: payload_bytes
    counts precisely 4 bytes/param, and the codec's framing overhead is
    bounded and accountable — unlike the old pytree estimate, which charged
    a flat 8 bytes for every Python scalar and nothing for structure."""
    n = 1000
    flat = jnp.zeros(n, jnp.float32)
    msg = {"batch": 10, "layers": {3: flat}}
    exact_array = 4 * n
    assert payload_bytes(msg) == exact_array + 8      # +8: the batch int
    wire = codec.encode("chain_put", msg)
    overhead = len(wire) - exact_array
    assert 0 < overhead < 128                         # framing only
    # old-style pytree payload of the same weights: same array bytes, but
    # the estimate cannot see framing, keys, or structure at all
    pytree_msg = {"batch": 10, "layers": {3: {"w": flat.reshape(40, 25)}}}
    assert payload_bytes(pytree_msg) == exact_array + 8
    assert len(codec.encode("chain_put", pytree_msg)) > exact_array


def test_transport_codec_round_trips_and_counts_wire_bytes():
    t = Transport(codec=True)
    t.register(0)
    t.register(1)
    x = jnp.arange(32.0, dtype=jnp.float32)
    assert t.send(0, 1, "act", (4, 2, x))
    msg = t.recv(1, timeout=0.5)
    assert msg.kind == "act"
    seg, b, arr = msg.payload
    assert (seg, b) == (4, 2)
    assert isinstance(arr, np.ndarray)            # fresh deserialized copy
    np.testing.assert_array_equal(arr, np.asarray(x))
    assert t.stats["bytes"] == len(codec.encode("act", (4, 2, x)))


@pytest.mark.live
def test_live_training_identical_with_wire_codec():
    """The full protocol round-tripped through bytes: same losses as the
    in-process object transport, proving every payload is wire-clean."""
    import jax

    from repro.runtime.live import LiveConfig, run_live_training
    from repro.runtime.protocol import ProtocolConfig
    from repro.runtime.workload import classification_batches, mlp_chain

    def run(wire):
        chain = mlp_chain(jax.random.PRNGKey(0), num_layers=8)
        data = classification_batches("mlp", 8, batch=16, seed=0)
        return run_live_training(chain, data, LiveConfig(
            num_workers=3, num_batches=14,
            protocol=ProtocolConfig(chain_every=5, global_every=10,
                                    repartition_first_at=10_000,
                                    repartition_every=10_000,
                                    detect_timeout=2.0),
            lr=0.1, wire_codec=wire))

    plain, coded = run(False), run(True)
    np.testing.assert_allclose(coded.losses, plain.losses, rtol=1e-5,
                               atol=1e-6)
    assert coded.transport_stats["bytes"] > 0


# ============== device-quantized passthrough (codec v3, tag 13) ==========

def _dq(shape=(4, 3), seed=3):
    from repro.runtime.qtensor import DeviceQuantized

    rng = np.random.default_rng(seed)
    C = shape[-1]
    q = rng.integers(0, 256, size=shape, dtype=np.uint8)
    lo = rng.standard_normal(C).astype("<f4")
    scale = np.abs(rng.standard_normal(C)).astype("<f4")
    return DeviceQuantized.from_arrays(q, lo, scale)


def test_device_quantized_round_trip_and_version():
    """Tag 13 frames stamp codec v3, round-trip every field bit-exactly,
    and pass the payload bytes through VERBATIM (zero-copy: the codes
    appear unmodified in the frame)."""
    from repro.runtime.qtensor import DeviceQuantized

    x = _dq((5, 2, 7))
    data = codec.encode("act", (2, 0, x))
    assert data[4] == 3                               # codec v3
    kind, payload = codec.decode(data)
    assert kind == "act" and payload[0] == 2
    y = payload[2]
    assert isinstance(y, DeviceQuantized)
    assert y.shape == x.shape
    assert y.data == x.data and y.lo == x.lo and y.scale == x.scale
    assert x.data in data                             # shipped as-is
    # a DeviceQuantized encodes as tag 13 under ANY tier (it is already
    # quantized); the tier only steers plain ndarrays
    for tier in codec.TIERS:
        assert codec.decode(codec.encode("act", x, tier=tier))[1].data \
            == x.data


def test_fused_tier_downgrades_plain_arrays_to_int8():
    """Plain f32 under int8-fused (e.g. replica snapshots) take the
    tag-12 path — only stage boundaries carry tag 13 — so the frame is
    v2, not v3."""
    x = _rand((6, 4))
    data = codec.encode("chain_put", {"w": x}, tier="int8-fused")
    assert data[4] == 2
    _, y = codec.decode(data)
    assert y["w"].dtype == np.float32
    # non-finite under the fused tier still falls back to exact v1
    nan = np.full((4,), np.nan, np.float32)
    assert codec.encode("act", nan, tier="int8-fused")[4] == 1


def test_truncated_compressed_payloads_rejected():
    """Regression: a short read must raise a clear error, never decode
    to a smaller tensor — for the int8 tag, the fused tag, and friends."""
    frames = {
        "int8": codec.encode("act", _rand((8, 4)), tier="int8"),
        "fp16": codec.encode("act", _rand((8, 4)), tier="fp16"),
        "f32": codec.encode("act", _rand((8, 4))),
        "fused": codec.encode("act", _dq((8, 4))),
    }
    for name, data in frames.items():
        for cut in (1, 4, len(data) // 2):
            with pytest.raises(ValueError, match="truncated|exhausted"):
                codec.decode(data[:-cut])
        with pytest.raises(ValueError, match="trailing"):
            codec.decode(data + b"\x00")
        with pytest.raises(ValueError, match="trailing"):
            codec.decode(data + data[-8:])


def test_corrupt_device_quantized_header_rejected():
    """Tampering the tag-13 channel count must fail loudly (it is
    redundant with dims[-1] precisely so corruption is detectable)."""
    import struct

    x = _dq((4, 3))
    data = bytearray(codec.encode("act", x))
    # locate the channel-count u32 right after tag|ndim|dims
    idx = data.index(bytes([13])) + 1 + 1 + 4 * len(x.shape)
    struct.pack_into("<I", data, idx, 99)
    with pytest.raises(ValueError, match="channel"):
        codec.decode(bytes(data))


def test_device_quantized_validates_byte_lengths():
    from repro.runtime.qtensor import DeviceQuantized

    with pytest.raises(ValueError, match="code bytes"):
        DeviceQuantized((4, 3), b"\x00" * 11, b"\x00" * 12, b"\x00" * 12)
    with pytest.raises(ValueError, match="channels"):
        DeviceQuantized((4, 3), b"\x00" * 12, b"\x00" * 8, b"\x00" * 12)
    with pytest.raises(ValueError, match="rank"):
        DeviceQuantized((), b"", b"", b"")
