"""Native C++ decoders vs their pure-Python fallbacks: differential tests.

The contract is that ``available()`` never changes observable behavior —
only speed. Every property here runs against BOTH implementations on the
same inputs and requires bit-identical outputs.
"""

import json

import numpy as np
import pytest

from torchkafka_tpu import native


def _both(fn_name, *args, **kw):
    """Run a native function and its forced-fallback twin."""
    fast = getattr(native, fn_name)(*args, **kw)
    saved = native._native
    try:
        native._native = None
        slow = getattr(native, fn_name)(*args, **kw)
    finally:
        native._native = saved
    return fast, slow


needs_native = pytest.mark.skipif(
    not native.available(), reason="native extension did not build"
)


class TestPackBits:
    """Sub-byte wire codec: C pack == NumPy pack == exact roundtrip
    through the device-side unpack for every bit width."""

    @needs_native
    @pytest.mark.parametrize("bits", [1, 7, 8, 11, 15, 16])
    def test_pack_differential(self, rng, bits):
        rows = rng.integers(0, 1 << bits, (33, 21), dtype=np.uint16)
        fast, slow = _both("pack_bits", rows, bits)
        np.testing.assert_array_equal(fast, slow)
        assert fast.shape == (33, native.packed_width(21, bits))

    @pytest.mark.parametrize("bits", [1, 5, 8, 13, 15, 16])
    @pytest.mark.parametrize("seq", [1, 7, 32, 33])
    def test_roundtrip_through_device_unpack(self, rng, bits, seq):
        from torchkafka_tpu.ops.bitpack import unpack_bits

        rows = rng.integers(0, 1 << bits, (17, seq), dtype=np.uint16)
        packed = native.pack_bits(rows, bits)
        got = np.asarray(unpack_bits(packed, bits, seq))
        np.testing.assert_array_equal(got, rows.astype(np.int32))

    def test_wire_savings(self):
        # The reason the codec exists: 15-bit vocab at 32 tokens = 60 bytes
        # vs 64 uint16.
        assert native.packed_width(32, 15) == 60

    def test_empty(self):
        out = native.pack_bits(np.empty((0, 8), np.uint16), 15)
        assert out.shape == (0, native.packed_width(8, 15))

    def test_bad_bits_rejected(self):
        with pytest.raises(ValueError):
            native.packed_width(8, 0)
        with pytest.raises(ValueError):
            native.packed_width(8, 17)


class TestGatherRows:
    @needs_native
    def test_exact_rows_differential(self, rng):
        vals = [rng.integers(0, 255, 16, dtype=np.uint8).tobytes() for _ in range(257)]
        fast, slow = _both("gather_rows", vals, 16, np.uint8)
        np.testing.assert_array_equal(fast, slow)

    @needs_native
    @pytest.mark.parametrize("dtype,pad", [(np.int32, -1), (np.float32, 0.5), (np.uint8, 7)])
    def test_ragged_rows_differential(self, rng, dtype, pad):
        item = np.dtype(dtype).itemsize
        vals = [
            rng.integers(0, 255, int(k), dtype=np.uint8).tobytes()
            for k in rng.integers(0, 8 * item + 3, 64)  # includes partial items
        ]
        fast, slow = _both("gather_rows", vals, 8, dtype, pad)
        np.testing.assert_array_equal(fast, slow)

    @needs_native
    def test_partial_trailing_item_truncated(self):
        out = native.gather_rows([b"\x01\x00\x00\x00\x02\x00"], 4, np.int32, pad=-1)
        assert out[0].tolist() == [1, -1, -1, -1]

    def test_empty_list(self):
        out = native.gather_rows([], 8, np.int32)
        assert out.shape == (0, 8)


class TestJsonTokens:
    @needs_native
    def test_differential_wellformed_and_malformed(self):
        vals = [
            json.dumps({"text": "hello world", "x": 1}).encode(),
            json.dumps({"x": {"text": "nested counts too"}}).encode(),
            b'{"text" : "spaced colon"}',
            b'{"text": 42}',  # not a string -> drop
            b'{"other": "field"}',  # missing -> drop
            b'{"text": "unterminated',  # -> drop
            b"not json at all",  # -> drop
            json.dumps({"text": "x" * 100}).encode(),  # truncation
        ]
        fast, slow = (
            r for r in _both("json_tokens_scan", vals, "text", 16, 0)
        )
        np.testing.assert_array_equal(fast[0], slow[0])
        np.testing.assert_array_equal(fast[1], slow[1])
        assert fast[1].tolist() == [1, 1, 1, 0, 0, 0, 0, 1]

    @needs_native
    def test_tokenization_is_utf8_bytes(self):
        toks, keep = native.json_tokens_scan([b'{"t": "AB"}'], "t", 4, pad_id=-1)
        assert keep[0] == 1
        assert toks[0].tolist() == [65, 66, -1, -1]

    @needs_native
    def test_escaped_quote_does_not_terminate(self):
        fast, slow = _both(
            "json_tokens_scan", [br'{"t": "a\"b"}'], "t", 8, 0
        )
        np.testing.assert_array_equal(fast[0], slow[0])
        assert fast[1][0] == 1


class TestProcessorIntegration:
    def test_fixed_width_uses_gather(self, rng):
        from torchkafka_tpu.source.records import Record
        from torchkafka_tpu.transform import fixed_width

        recs = [
            Record("t", 0, i, rng.integers(0, 9, 4).astype(np.int32).tobytes())
            for i in range(7)
        ]
        stacked, keep = fixed_width(4, np.int32)(recs)
        assert stacked.shape == (7, 4) and keep is None

    def test_json_tokens_processor_drops(self):
        from torchkafka_tpu.source.records import Record
        from torchkafka_tpu.transform import json_tokens

        recs = [
            Record("t", 0, 0, b'{"text": "ok"}'),
            Record("t", 0, 1, b'{"nope": 1}'),
        ]
        stacked, keep = json_tokens("text", 8)(recs)
        assert keep.tolist() == [True, False]
        assert stacked.shape == (1, 8)


class TestFuzzDifferential:
    """Random-bytes fuzz: the C++ scanners must agree bit-for-bit with the
    NumPy fallbacks on arbitrary garbage (truncated escapes, embedded
    quotes/braces/NULs, zero-length values) and never crash — a malformed
    Kafka record must only ever become a dropped row."""

    @needs_native
    @pytest.mark.parametrize("seed", range(8))
    def test_json_tokens_random_garbage(self, seed):
        rng = np.random.default_rng(seed)
        vals = []
        for _ in range(64):
            n = int(rng.integers(0, 60))
            raw = bytes(rng.integers(0, 256, n, dtype=np.uint8))
            if rng.random() < 0.4:  # bias toward json-ish shapes
                raw = b'{"text": "' + raw.replace(b'"', b"") + b'"}'
            if rng.random() < 0.2:
                raw = raw[: max(0, n - 3)]  # truncate mid-structure
            vals.append(raw)
        fast, slow = _both("json_tokens_scan", vals, "text", 12, 0)
        np.testing.assert_array_equal(fast[0], slow[0])
        np.testing.assert_array_equal(fast[1], slow[1])

    @needs_native
    @pytest.mark.parametrize("seed", range(4))
    def test_gather_rows_random_lengths(self, seed):
        rng = np.random.default_rng(100 + seed)
        vals = [
            bytes(rng.integers(0, 256, int(rng.integers(0, 40)), dtype=np.uint8))
            for _ in range(64)
        ]
        fast, slow = _both("gather_rows", vals, 6, np.int32, -1)
        np.testing.assert_array_equal(fast, slow)


@needs_native
def test_concurrent_builds_all_succeed(tmp_path):
    """Two processes that build at once into an empty directory (test
    workers on a fresh checkout) both end with the binary in place: each
    compiles to a temporary name of its own. With one shared name the
    first ``os.replace`` took the file from under the other, whose build
    then reported failure and whose ``needs_native`` tests skipped."""
    import os
    import subprocess
    import sys
    import time

    child = (
        "import os, sys, time\n"
        "import torchkafka_tpu.native as n\n"
        "d = sys.argv[1]\n"
        "n._HERE, n._SO = d, os.path.join(d, os.path.basename(n._SO))\n"
        "open(os.path.join(d, f'ready.{os.getpid()}'), 'w').close()\n"
        "while not os.path.exists(os.path.join(d, 'go')):\n"
        "    time.sleep(0.01)\n"
        "sys.exit(0 if n._build() and os.path.exists(n._SO) else 1)\n"
    )
    procs = [
        subprocess.Popen([sys.executable, "-c", child, str(tmp_path)])
        for _ in range(2)
    ]
    try:
        deadline = time.monotonic() + 120
        while len(list(tmp_path.glob("ready.*"))) < 2:
            assert time.monotonic() < deadline, "the builders did not start"
            assert all(p.poll() is None for p in procs)
            time.sleep(0.05)
        (tmp_path / "go").touch()
        assert [p.wait(timeout=240) for p in procs] == [0, 0]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    built = [f for f in os.listdir(tmp_path) if f.startswith("_tk_native")]
    assert built == [os.path.basename(native._SO)]  # and no temporary left
