"""Tests for foundation utilities."""

import io
import random
import threading

import pytest

from cometbft_tpu.utils.bit_array import BitArray
from cometbft_tpu.utils.log import Logger, parse_log_level
from cometbft_tpu.utils.protoio import (
    ProtoReader,
    ProtoWriter,
    decode_uvarint,
    encode_uvarint,
    length_prefixed,
    read_length_prefixed,
)
from cometbft_tpu.utils.service import AlreadyStartedError, BaseService


class TestService:
    def test_start_stop_idempotency(self):
        svc = BaseService(name="t")
        svc.start()
        assert svc.is_running()
        with pytest.raises(AlreadyStartedError):
            svc.start()
        svc.stop()
        assert not svc.is_running()
        svc.stop()  # idempotent

    def test_quit_event_wakes_waiter(self):
        svc = BaseService(name="t")
        svc.start()
        woke = threading.Event()

        def waiter():
            svc.wait(5)
            woke.set()

        t = threading.Thread(target=waiter)
        t.start()
        svc.stop()
        t.join(5)
        assert woke.is_set()

    def test_on_start_failure_resets(self):
        class Failing(BaseService):
            def on_start(self):
                raise RuntimeError("boom")

        svc = Failing(name="f")
        with pytest.raises(RuntimeError, match="boom"):
            svc.start()
        # after a failed start, start() may be retried (not AlreadyStartedError)
        with pytest.raises(RuntimeError, match="boom"):
            svc.start()


class TestLog:
    def test_logfmt_output_and_levels(self):
        sink = io.StringIO()
        log = Logger(sink=sink, level="info")
        log.debug("hidden")
        log.info("hello", height=5)
        out = sink.getvalue()
        assert "hidden" not in out
        assert "msg=hello" in out and "height=5" in out

    def test_module_filtering(self):
        base, mods = parse_log_level("p2p:debug,consensus:error,*:info")
        assert base == "info"
        assert mods == {"p2p": "debug", "consensus": "error"}
        sink = io.StringIO()
        log = Logger(sink=sink, level=base, module_levels=mods)
        log.with_fields(module="consensus").info("quiet")
        log.with_fields(module="p2p").debug("loud")
        out = sink.getvalue()
        assert "quiet" not in out
        assert "loud" in out


class TestProtoIO:
    def test_uvarint_roundtrip(self):
        for n in [0, 1, 127, 128, 300, 2**32, 2**63 - 1, 2**64 - 1]:
            enc = encode_uvarint(n)
            dec, off = decode_uvarint(enc)
            assert dec == n and off == len(enc)

    @pytest.mark.parametrize("nbytes", range(1, 11))
    def test_uvarint_is_seven_bits_a_byte(self, nbytes):
        """Each width's edges, and random values between them, against
        the plain loop: the unrolled two- to five-byte forms too."""

        def plain(n):
            out = bytearray()
            while n > 0x7F:
                out.append(n & 0x7F | 0x80)
                n >>= 7
            out.append(n)
            return bytes(out)

        lo = 0 if nbytes == 1 else 1 << 7 * (nbytes - 1)
        hi = min(1 << 7 * nbytes, 1 << 64)
        rng = random.Random(nbytes)
        for n in [lo, lo + 1, hi - 2, hi - 1,
                  *(rng.randrange(lo, hi) for _ in range(200))]:
            enc = encode_uvarint(n)
            assert enc == plain(n) and len(enc) == nbytes, n
            assert decode_uvarint(enc) == (n, nbytes)

    def test_writer_reader_roundtrip(self):
        w = ProtoWriter()
        w.varint(1, 2)
        w.sfixed64(2, -5)
        w.string(6, "chain-A")
        w.bytes_(4, b"\x01\x02")
        data = w.finish()
        fields = ProtoReader(data).to_dict()
        assert fields[1] == [2]
        assert fields[2] == [(-5) & 0xFFFFFFFFFFFFFFFF]
        assert fields[6] == [b"chain-A"]
        assert fields[4] == [b"\x01\x02"]

    def test_zero_fields_omitted(self):
        w = ProtoWriter()
        w.varint(1, 0)
        w.sfixed64(2, 0)
        w.string(3, "")
        assert w.finish() == b""

    def test_message_presence(self):
        w = ProtoWriter()
        w.message(1, b"")  # present empty message
        w.message(2, None)  # absent
        assert w.finish() == b"\x0a\x00"

    def test_length_prefixed(self):
        framed = length_prefixed(b"hello")
        payload, off = read_length_prefixed(framed)
        assert payload == b"hello" and off == len(framed)

    def test_deterministic(self):
        def enc():
            w = ProtoWriter()
            w.varint(1, 2)
            w.sfixed64(2, 1234)
            w.string(6, "chain")
            return w.finish()

        assert enc() == enc()


class TestBitArray:
    def test_set_get(self):
        ba = BitArray(10)
        assert ba.set_index(3, True)
        assert ba.get_index(3)
        assert not ba.get_index(4)
        assert not ba.set_index(10, True)  # out of range
        assert not ba.get_index(-1)

    def test_ops(self):
        a = BitArray(8)
        b = BitArray(8)
        a.set_index(1, True)
        b.set_index(1, True)
        b.set_index(2, True)
        assert b.sub(a).true_indices() == [2]
        assert a.or_(b).true_indices() == [1, 2]
        assert a.and_(b).true_indices() == [1]
        assert a.not_().true_indices() == [0, 2, 3, 4, 5, 6, 7]

    def test_full_empty_pick(self, rng):
        ba = BitArray(5)
        assert ba.is_empty()
        _, ok = ba.pick_random(rng)
        assert not ok
        for i in range(5):
            ba.set_index(i, True)
        assert ba.is_full()
        idx, ok = ba.pick_random(rng)
        assert ok and 0 <= idx < 5

    def test_bytes_roundtrip(self):
        ba = BitArray(12)
        ba.set_index(0, True)
        ba.set_index(11, True)
        rt = BitArray.from_bytes(12, ba.to_bytes())
        assert rt == ba
