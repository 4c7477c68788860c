#!/usr/bin/env python3
"""Native parity micro-tests (`make check`): the fast gate that a freshly
built libredpanda_native.so computes what it claims, on THIS host's
dispatch path (hardware CRC if the CPU has SSE4.2, AVX2 classification if
it has AVX2 — the same binary must be correct on every tier).

Pure ctypes + stdlib: runnable straight from native/ with no package
import, so a cross-compiled or prebuilt .so can be checked in isolation.
"""

import ctypes
import os
import struct
import sys
import zlib

HERE = os.path.dirname(os.path.abspath(__file__))
SO = os.path.join(HERE, "libredpanda_native.so")


def crc32c_ref(data: bytes) -> int:
    """Bit-reflected CRC-32C (Castagnoli) reference, table-free."""
    crc = 0xFFFFFFFF
    for byte in data:
        crc ^= byte
        for _ in range(8):
            crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
    return crc ^ 0xFFFFFFFF


def zigzag(v: int) -> bytes:
    u = (v << 1) ^ (v >> 63) if v < 0 else v << 1
    out = bytearray()
    while u >= 0x80:
        out.append((u & 0x7F) | 0x80)
        u >>= 7
    out.append(u)
    return bytes(out)


def frame_record(seq: int, value: bytes | None) -> bytes:
    body = bytearray(b"\x00")
    body += zigzag(0)
    body += zigzag(seq)
    body += zigzag(-1)
    if value is None:
        body += zigzag(-1)
    else:
        body += zigzag(len(value)) + value
    body += zigzag(0)
    return zigzag(len(body)) + bytes(body)


def main() -> int:
    dll = ctypes.CDLL(SO)
    failures = 0

    def check(name, ok):
        nonlocal failures
        print(f"  {'ok' if ok else 'FAIL'}  {name}")
        if not ok:
            failures += 1

    # ---- CRC: runtime-dispatched implementation vs pure-python reference
    dll.rp_crc32c.restype = ctypes.c_uint32
    dll.rp_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    for blob in (b"", b"a", b"123456789", bytes(range(256)) * 9 + b"tail"):
        got = dll.rp_crc32c(blob, len(blob))
        check(f"crc32c len={len(blob)}", got == crc32c_ref(blob))

    # ---- structural vs scalar parse: identical span tables
    has2 = hasattr(dll, "rp_explode_find2")
    check("rp_explode_find2 symbol present", has2)
    if not has2:
        return 1
    values = [
        b'{"level":"error","code":5,"msg":"hello"}',
        b'{"a":"esc\\"aped","level":"in\\\\fo","code":-3.5e2,"msg":""}',
        b'{"level":"x","nested":{"a":[1,{"q":"}"}]},"code":true,"msg":null}',
        "{\"level\":\"ünïcødé\",\"code\":42,\"msg\":\"πλ\"}".encode(),
        b'{"msg":"' + b"\\\"" * 64 + b'","level":"error","code":9}',
        b'{"truncated":"unterminated',
        b"[1,2]",
        b"{}",
        None,  # null value
    ]
    payload = b"".join(
        frame_record(i, v) for i, v in enumerate(values)
    )
    n = len(values)
    paths = [b"level", b"code", b"msg", b"nested"]
    blob = b"".join(paths)
    k = len(paths)
    path_off = (ctypes.c_int32 * k)(*[
        sum(len(p) for p in paths[:i]) for i in range(k)
    ])
    path_len = (ctypes.c_int32 * k)(*[len(p) for p in paths])

    def tables():
        return (
            (ctypes.c_int64 * n)(), (ctypes.c_int32 * n)(),
            (ctypes.c_int8 * (n * k))(), (ctypes.c_int64 * (n * k))(),
            (ctypes.c_int64 * (n * k))(),
        )

    p_len = (ctypes.c_int32 * 1)(len(payload))
    counts = (ctypes.c_int32 * 1)(n)
    p_off = (ctypes.c_int64 * 1)(0)
    a = tables()
    dll.rp_explode_find.restype = ctypes.c_int64
    got = dll.rp_explode_find(
        payload, p_off, p_len, counts, 1, blob, path_off, path_len, k,
        a[0], a[1], a[2], a[3], a[4],
    )
    check("scalar parse count", got == n)
    ptrs = (ctypes.c_char_p * 1)(payload)
    joined = ctypes.create_string_buffer(len(payload))
    b = tables()
    dll.rp_explode_find2.restype = ctypes.c_int64
    got2 = dll.rp_explode_find2(
        ptrs, p_len, counts, 1, joined, blob, path_off, path_len, k,
        b[0], b[1], b[2], b[3], b[4],
    )
    check("structural parse count", got2 == n)
    check("joined blob copy", joined.raw == payload)
    check("val_off parity", list(a[0]) == list(b[0]))
    check("val_len parity", list(a[1]) == list(b[1]))
    check("types parity", list(a[2]) == list(b[2]))
    span_ok = all(
        a[2][i] == 0 or (a[3][i] == b[3][i] and a[4][i] == b[4][i])
        for i in range(n * k)
    )
    check("span parity (found paths)", span_ok)

    # ---- gather framing round trip (rp_frame_gather)
    if hasattr(dll, "rp_frame_gather"):
        dll.rp_frame_gather.restype = ctypes.c_int64
        vals = [v for v in values if v is not None]
        src = b"".join(vals)
        offs, lens, pos = [], [], 0
        for v in vals:
            offs.append(pos)
            lens.append(len(v))
            pos += len(v)
        nn = len(vals)
        keep = (ctypes.c_uint8 * nn)(*([1] * nn))
        dst = ctypes.create_string_buffer(len(src) + 16 * nn + 16)
        kept = ctypes.c_int32()
        ln = dll.rp_frame_gather(
            src, (ctypes.c_int64 * nn)(*offs), (ctypes.c_int32 * nn)(*lens),
            keep, nn, dst, ctypes.byref(kept),
        )
        expect = b"".join(frame_record(i, v) for i, v in enumerate(vals))
        check("frame_gather bytes", dst.raw[:ln] == expect and kept.value == nn)

    # ---- pointer-table gather (rp_frame_many_gather_ptrs): one source
    # buffer a range, offsets relative to it; same bytes as the joined gather
    if hasattr(dll, "rp_frame_many_gather_ptrs"):
        dll.rp_frame_many_gather_ptrs.restype = ctypes.c_int64
        vals = [v for v in values if v is not None]
        split = len(vals) // 2
        groups = [vals[:split], vals[split:]]
        bufs = [b"\xff" + b"".join(g) for g in groups]  # values start at 1
        offs, lens = [], []
        for g in groups:
            pos = 1
            for v in g:
                offs.append(pos)
                lens.append(len(v))
                pos += len(v)
        nn = len(vals)
        keep_list = [i % 3 != 1 for i in range(nn)]
        starts = (ctypes.c_int64 * 2)(0, split)
        ends = (ctypes.c_int64 * 2)(split, nn)
        src_lens = (ctypes.c_int64 * 2)(*(len(b) for b in bufs))

        def gather_ptrs(off_list):
            dst = ctypes.create_string_buffer(sum(lens) + 16 * nn + 16)
            out_off = (ctypes.c_int64 * 2)()
            out_len = (ctypes.c_int64 * 2)()
            out_kept = (ctypes.c_int32 * 2)()
            total = dll.rp_frame_many_gather_ptrs(
                (ctypes.c_char_p * 2)(*bufs), src_lens,
                (ctypes.c_int64 * nn)(*off_list), (ctypes.c_int32 * nn)(*lens),
                (ctypes.c_uint8 * nn)(*keep_list), starts, ends, ctypes.c_int64(2), dst,
                out_off, out_len, out_kept,
            )
            parts = [
                (dst.raw[out_off[r] : out_off[r] + out_len[r]], out_kept[r])
                for r in range(2)
            ]
            return total, parts

        total, parts = gather_ptrs(offs)
        expect = []
        for g, lo in ((groups[0], 0), (groups[1], split)):
            kept = [v for i, v in enumerate(g) if keep_list[lo + i]]
            expect.append(
                (b"".join(frame_record(i, v) for i, v in enumerate(kept)), len(kept))
            )
        check(
            "frame_many_gather_ptrs bytes",
            parts == expect and total == sum(len(p) for p, _ in expect),
        )
        bad = list(offs)
        bad[-1] = len(bufs[1])  # a span that ends past ITS buffer
        check("frame_many_gather_ptrs bounds", gather_ptrs(bad)[0] == -1)

    # ---- pointer-table pack (rp_pack_rows_ptrs): the payload lane's whole
    # staging matrix in one crossing, or (a row selection) one part of a
    # launch staged by width class; parity with rp_pack_rows a batch plus
    # the length column, into a matrix that held 0xFF: the whole table and
    # selections of its rows, at a stride narrower than the longest values
    # (they stage length 0) and a narrower one still, pad rows cleared; -1
    # and nothing written on a bad span, a row outside the table, rows that
    # do not ascend
    if hasattr(dll, "rp_pack_rows_ptrs"):
        dll.rp_pack_rows_ptrs.restype = ctypes.c_int64
        dll.rp_pack_rows.restype = ctypes.c_int32
        groups = [values[:4], [], values[4:]]  # the null value rides last
        bufs = [b"\xff" + b"".join(v or b"" for v in g) for g in groups]
        offs, lens, bounds, pos_row = [], [], [], 0
        for g in groups:
            pos = 1
            for v in g:
                offs.append(pos)
                lens.append(-1 if v is None else len(v))
                pos += len(v or b"")
            bounds.append((pos_row, pos_row + len(g)))
            pos_row += len(g)
        nn, pad = len(offs), 5
        starts = (ctypes.c_int64 * 3)(*(s for s, _ in bounds))
        ends = (ctypes.c_int64 * 3)(*(e for _, e in bounds))
        src_lens = (ctypes.c_int64 * 3)(*(len(b) for b in bufs))

        def pack_ptrs(off_list, sel, row):
            """(rc, dst) of one crossing; ``sel`` None: the whole table."""
            k, stride = nn if sel is None else len(sel), row + 8
            dst = ctypes.create_string_buffer(
                b"\xff" * ((k + pad) * stride), (k + pad) * stride
            )
            rc = dll.rp_pack_rows_ptrs(
                (ctypes.c_char_p * 3)(*bufs), src_lens,
                (ctypes.c_int64 * nn)(*off_list), (ctypes.c_int32 * nn)(*lens),
                starts, ends, ctypes.c_int64(3),
                None if sel is None else (ctypes.c_int64 * max(k, 1))(*sel),
                ctypes.c_int64(k), dst, ctypes.c_int64(k + pad),
                ctypes.c_size_t(row),
            )
            return rc, dst.raw

        def staged_rows(row):
            """The table's rows as rp_pack_rows stages them a batch, plus
            the length column."""
            stride, out = row + 8, []
            for buf, (s, e) in zip(bufs, bounds):
                k = e - s
                part = ctypes.create_string_buffer(max(k * stride, 1))
                dll.rp_pack_rows(
                    buf, (ctypes.c_int64 * k)(*offs[s:e]),
                    (ctypes.c_int32 * k)(*lens[s:e]), ctypes.c_size_t(k), part,
                    ctypes.c_size_t(stride),
                )
                for i in range(k):
                    ln = lens[s + i]
                    staged_len = ln if 0 <= ln <= row else 0
                    r0 = part.raw[i * stride : i * stride + row]
                    out.append(r0 + struct.pack("<I", staged_len) + b"\0" * 4)
            return out

        for row, sel in ((48, None), (48, [0, 2, 3, nn - 1]), (16, [1, nn - 2]),
                         (48, list(range(nn))), (48, [])):
            rows = staged_rows(row)
            rc, got = pack_ptrs(offs, sel, row)
            what = "the table" if sel is None else f"{len(sel)} rows"
            check(
                f"pack_rows_ptrs bytes (stride {row}, {what})",
                rc == 0
                and got == b"".join(rows[i] for i in (range(nn) if sel is None else sel))
                + b"\0" * (pad * (row + 8)),
            )
        check(
            "pack_rows_ptrs stages oversize/null as length 0",
            any(ln > 48 for ln in lens) and lens[-1] == -1,
        )
        bad = list(offs)
        bad[3] = len(bufs[0])  # a span that ends past ITS buffer

        def untouched(k):
            return b"\xff" * ((k + pad) * 56)

        check("pack_rows_ptrs bounds (-1, nothing written)",
              pack_ptrs(bad, None, 48) == (-1, untouched(nn))
              and pack_ptrs(bad, [0, 3], 48) == (-1, untouched(2)))
        check("pack_rows_ptrs skips an unselected bad span",
              pack_ptrs(bad, [0, 2], 48)[0] == 0)
        check("pack_rows_ptrs rows must ascend",
              pack_ptrs(offs, [2, 1], 48) == (-1, untouched(2))
              and pack_ptrs(offs, [1, 1], 48) == (-1, untouched(2)))
        check("pack_rows_ptrs rows inside the table",
              pack_ptrs(offs, [0, nn], 48) == (-1, untouched(2))
              and pack_ptrs(offs, [-1, 0], 48) == (-1, untouched(2)))

    # ---- the seal, many batches a call, against the Python seal
    # (models/record.py reseal: the Kafka CRC over the big-endian header
    # prefix + the stored payload, the internal header CRC over the 57
    # little-endian bytes after it), and its frames against the
    # many-frames decompress
    if hasattr(dll, "rp_seal_many"):
        dll.rp_seal_available.restype = ctypes.c_int32
        dll.rp_seal_many.restype = ctypes.c_int64
        dll.rp_zstd_frame_sizes.restype = ctypes.c_int64
        dll.rp_zstd_uncompress_many.restype = ctypes.c_int64

        def py_seal(stored, attrs, kept, btype, ts0, ts1):
            crc = crc32c_ref(
                struct.pack(">hiqqqhii", attrs, kept - 1, ts0, ts1, -1, -1, -1, kept) + stored)
            header_crc = crc32c_ref(struct.pack(
                "<iqbIHiqqqhii", 61 + len(stored), 0, btype, crc, attrs, kept - 1,
                ts0, ts1, -1, -1, -1, kept))
            return crc, header_crc

        doc = b'{"level":"warn","code":%d,"msg":"the seal in one crossing"}'
        six = b"".join(frame_record(i, doc % i) for i in range(6))

        def padded_to(size):  # seven records, the last one's value as long as it takes
            return next(p for p in (six + frame_record(6, b"p" * k) for k in range(size))
                        if len(p) == size)

        payloads = [b"", padded_to(511), padded_to(512),
                    b"".join(frame_record(i, doc % (i * 7)) for i in range(40)),
                    frame_record(0, b""), b"never sealed"]
        kept = [0, 7, 7, 40, 1, 0]
        types = [1, 1, 5, 1, 1, 1]
        ts0 = [0, 1700000000000, 1700000000001, -1, 5, 0]
        ts1 = [0, 1700000000500, 1700000000001, 2**40, 5, 0]
        n = len(payloads)
        check("seal_many fixture straddles the threshold",
              [len(p) for p in payloads[1:3]] == [511, 512])

        def seal(codec, dst_cap, n_threads=4):
            dst = ctypes.create_string_buffer(max(dst_cap, 1))
            out_off = (ctypes.c_int64 * n)()
            out_len = (ctypes.c_int64 * n)()
            out_attrs = (ctypes.c_int32 * n)()
            crc = (ctypes.c_uint32 * n)()
            header_crc = (ctypes.c_uint32 * n)()
            rc = dll.rp_seal_many(
                (ctypes.c_char_p * n)(*payloads),
                (ctypes.c_int64 * n)(*(len(p) for p in payloads)),
                (ctypes.c_int32 * n)(*kept), (ctypes.c_int8 * n)(*types),
                (ctypes.c_int64 * n)(*ts0), (ctypes.c_int64 * n)(*ts1),
                ctypes.c_int64(n), ctypes.c_int64(512), ctypes.c_int32(codec),
                ctypes.c_int32(3), dst, ctypes.c_int64(dst_cap), out_off, out_len,
                out_attrs, crc, header_crc, ctypes.c_int32(n_threads),
            )
            return rc, dst, list(out_off), list(out_len), list(out_attrs), list(crc), list(header_crc)

        rc, _, off, ln, attrs, crc, hcrc = seal(0, 0)
        check("seal_many codec none: all stored, skipped jobs marked",
              rc == 0 and attrs == [0] * n and off == [-1] * n
              and ln == [-1, 511, 512, len(payloads[3]), len(payloads[4]), -1])
        check("seal_many codec none CRCs == python seal", all(
            (crc[b], hcrc[b]) == py_seal(payloads[b], 0, kept[b], types[b], ts0[b], ts1[b])
            for b in range(n) if kept[b]))
        check("seal_many refuses a codec it does not have", seal(3, 1 << 16)[0] == -1)
        if dll.rp_seal_available():
            rc, dst, off, ln, attrs, crc, hcrc = seal(4, 1 << 16)
            check("seal_many zstd: frames from the threshold on",
                  rc == 0 and attrs == [0, 0, 4, 4, 0, 0] and off[2] == 0 and off[3] > ln[2]
                  and ln[1] == 511 and 0 < ln[3] < len(payloads[3]))
            stored = [dst.raw[off[b] : off[b] + ln[b]] if attrs[b] else payloads[b]
                      for b in range(n)]
            check("seal_many zstd CRCs == python seal", all(
                (crc[b], hcrc[b]) == py_seal(stored[b], attrs[b], kept[b], types[b], ts0[b], ts1[b])
                for b in range(n) if kept[b]))
            frames = [stored[2], stored[3]]
            f_off = (ctypes.c_int64 * 2)()
            f_len = (ctypes.c_int64 * 2)()
            src = (ctypes.c_char_p * 2)(*frames)
            src_lens = (ctypes.c_int64 * 2)(*(len(f) for f in frames))
            total = dll.rp_zstd_frame_sizes(src, src_lens, ctypes.c_int64(2), f_off, f_len)
            check("seal_many frames state their content size",
                  list(f_len) == [len(payloads[2]), len(payloads[3])])
            back = ctypes.create_string_buffer(max(total, 1))
            failed = dll.rp_zstd_uncompress_many(
                src, src_lens, ctypes.c_int64(2), back, ctypes.c_int64(total), f_off, f_len,
                ctypes.c_int32(1))
            check("seal_many frames decompress to their payloads",
                  failed == 0 and back.raw[:total] == payloads[2] + payloads[3])
            one = seal(4, 1 << 16, n_threads=1)
            check("seal_many threads change nothing", (one[0], list(one[2:])) == (rc, [off, ln, attrs, crc, hcrc]))
            rc, _, off, ln, attrs, crc, hcrc = seal(4, 600)  # room for the first frame alone
            check("seal_many dst too small: that job alone is left unsealed",
                  rc == 1 and ln[3] == -1 and ln[2] > 0 and attrs[2] == 4
                  and (crc[4], hcrc[4]) == py_seal(payloads[4], 0, 1, 1, 5, 5))
        else:
            check("seal_many without libzstd serves no zstd job", seal(4, 1 << 16)[0] == -1)

    # ---- the log's framing, a list of batches a call, against the Python
    # append (models/record.py with_base_offset(..).encode_internal(): the
    # stated header with the assigned base offset and the header CRC over
    # the 57 little-endian bytes after it, then the payload) and against
    # verify_kafka_crc (the Kafka CRC over the big-endian prefix + payload)
    if hasattr(dll, "rp_frame_internal_many"):
        import threading

        dll.rp_frame_internal_many.restype = ctypes.c_int64
        dll.rp_frame_internal_many.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ]
        pack = "<IiqbiHiqqqhii"

        def sealed(payload, btype, attrs, delta, ts0, ts1, pid, epoch, seq, count):
            crc = crc32c_ref(struct.pack(">Hiqqqhii", attrs, delta, ts0, ts1, pid, epoch, seq, count) + payload)
            return [0xDEAD, 61 + len(payload), 99, btype, crc - (1 << 32) if crc >> 31 else crc,
                    attrs, delta, ts0, ts1, pid, epoch, seq, count]

        def py_frame(fields, payload, base):
            fields = fields[:2] + [base] + fields[3:]
            header_crc = crc32c_ref(struct.pack("<" + pack[2:], *fields[1:]))
            return struct.pack(pack, header_crc, *fields[1:]) + payload, header_crc

        bodies = [b"", b"x", bytes(range(256)) * 12, b"\xff" * 61, b"tail" * 700]
        heads = [
            sealed(bodies[0], 1, 0, 0, 0, 0, -1, -1, -1, 0),
            sealed(bodies[1], 1, 4, 0, 1700000000000, 1700000000000, -1, -1, -1, 1),
            sealed(bodies[2], 5, 0x14, 31, 1700000000001, 1700000000032, 7, 3, 100, 32),
            sealed(bodies[3], 1, 0xFFFF, 2**31 - 2, -1, 2**62, 2**62, -2, 2**31 - 1, 2**31 - 1),
            sealed(bodies[4], 1, 4, 6, 5, 11, -1, -1, -1, 7),
        ]
        nb = len(bodies)

        def frame(hs, first, verify, cap=None, payloads=bodies):
            joined = b"".join(struct.pack(pack, *h) for h in hs)
            total = sum(h[1] for h in hs)
            cap = total if cap is None else cap
            dst = ctypes.create_string_buffer(max(cap, 1))
            out = (ctypes.c_int64 * len(hs))()
            rc = dll.rp_frame_internal_many(
                joined, (ctypes.c_char_p * len(hs))(*payloads), len(hs), first, verify,
                dst, cap, out)
            return rc, dst.raw[: max(rc, 0)], list(out)

        def py_frames(hs, first, skip=()):
            blob, crcs, nxt = b"", [], first
            for b, h in enumerate(hs):
                if b in skip:
                    crcs.append(-1)
                    continue
                f, c = py_frame(h, bodies[b], nxt)
                blob += f
                crcs.append(c)
                nxt += h[6] + 1
            return len(blob), blob, crcs

        for verify in (0, 1):
            check(f"frame_internal_many == with_base_offset().encode_internal(), verify={verify}",
                  frame(heads, 2**40 + 5, verify) == py_frames(heads, 2**40 + 5))
        check("frame_internal_many: one batch, as a produce appends it",
              frame(heads[2:3], 0, 0, payloads=bodies[2:3])
              == (heads[2][1], py_frame(heads[2], bodies[2], 0)[0], [py_frame(heads[2], bodies[2], 0)[1]]))
        for bad in ((0,), (2,), (4,), (1, 2), tuple(range(nb))):
            torn = [(b[:len(b) // 2] + bytes([b[len(b) // 2] ^ 1]) + b[len(b) // 2 + 1:])
                    if i in bad and b else b for i, b in enumerate(bodies)]
            hs = [h if (i not in bad or bodies[i]) else h[:4] + [h[4] ^ 1] + h[5:]
                  for i, h in enumerate(heads)]  # an empty payload: tear the stated CRC
            check(f"frame_internal_many verify leaves {bad} out, neighbours contiguous",
                  frame(hs, 7, 1, payloads=torn) == py_frames(heads, 7, skip=bad))
            got = frame(hs, 7, 0, payloads=torn)
            check(f"frame_internal_many without verify frames {bad} as stated",
                  got[0] == sum(h[1] for h in heads) and -1 not in got[2])
        total = sum(h[1] for h in heads)
        check("frame_internal_many dst too small: -1", frame(heads, 0, 1, cap=total - 1)[0] == -1)
        check("frame_internal_many dst with room to spare", frame(heads, 0, 1, cap=total + 99) == py_frames(heads, 0))
        check("frame_internal_many size_bytes under 61: -1",
              frame([heads[0][:1] + [60] + heads[0][2:]], 0, 0, payloads=bodies[:1])[0] == -1)
        check("frame_internal_many empty list", frame([], 3, 1, payloads=[]) == (0, b"", []))
        want = py_frames(heads, 11)
        results = []

        def many_times():
            results.append(all(frame(heads, 11, 1) == want for _ in range(200)))

        threads = [threading.Thread(target=many_times) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        check("frame_internal_many from four threads at once", results == [True] * 4)

        # ---- a scanning read's walk over a window's frames, a window a
        # call, against the frames above and Segment.scan's rules spelt out
        if hasattr(dll, "rp_scan_internal_frames"):
            dll.rp_scan_internal_frames.restype = ctypes.c_int32
            dll.rp_scan_internal_frames.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_uint64, ctypes.c_uint64,
                ctypes.c_void_p, ctypes.c_int64,
            ]
            _, window, hcrcs = py_frames(heads, 100)
            known = (1 << 20) - 2  # types 1..19
            no_max = 2**63 - 1

            def scan(win, at=0, start=0, hi=no_max, budget=1 << 30, mask=known, cap=16):
                table = (ctypes.c_int64 * (4 + 14 * cap))()
                status = dll.rp_scan_internal_frames(
                    win, len(win), at, start, hi, budget, known, mask, table, cap)
                n = table[0]
                return status, list(table[1:4]), [list(table[4 + 14 * r: 18 + 14 * r]) for r in range(n)]

            def py_rows(keep):
                rows, at, nxt = [], 0, 100
                for b, h in enumerate(heads):
                    if b in keep:
                        rows.append([at, hcrcs[b], h[1], nxt, h[3], h[4] & 0xFFFFFFFF] + h[5:])
                    at += h[1]
                    nxt += h[6] + 1
                return rows

            sizes = [h[1] for h in heads]
            ends = [sum(sizes[: b + 1]) for b in range(nb)]
            bases = [r[3] for r in py_rows(range(nb))]
            checks = {
                "every frame kept, the window ends": scan(window)
                == (1, [ends[-1], ends[-1], ends[-1]], py_rows(range(nb))),
                "start_offset inside a batch keeps it": scan(window, start=bases[3] + 5)
                == (1, [ends[-1], ends[-1], sizes[3] + sizes[4]], py_rows((3, 4))),
                "max_offset: the frame past it is not consumed": scan(window, hi=bases[3] - 1)
                == (0, [ends[2], ends[2], ends[2]], py_rows((0, 1, 2))),
                "a filtered type after the last kept frame is consumed, not covered":
                scan(window[: ends[2]], mask=1 << 1)
                == (1, [ends[2], ends[1], ends[1]], py_rows((0, 1))),
                "the budget ends the walk once taken": scan(window, budget=sizes[0] + 1)
                == (0, [ends[1], ends[1], ends[1]], py_rows((0, 1))),
                "a window that ends inside a header": scan(window[: ends[1] + 60])
                == (1, [ends[1], ends[1], ends[1]], py_rows((0, 1))),
                "a window that ends inside a payload": scan(window[: ends[2] - 1])
                == (1, [ends[1], ends[1], ends[1]], py_rows((0, 1))),
                "a full table": scan(window, cap=2)
                == (3, [ends[1], ends[1], ends[1]], py_rows((0, 1))),
                "from a position on": scan(window, at=ends[1])[2] == py_rows((2, 3, 4)),
            }
            for byte in (0, 9, 16, 30, 60):  # header_crc, base offset, type, a timestamp, the count
                torn = bytearray(window)
                torn[ends[0] + byte] ^= 0x40
                checks[f"header byte {byte} flipped: not sound"] = scan(bytes(torn)) == (
                    2, [ends[0], ends[0], ends[0]], py_rows((0,)))
            torn = bytearray(window)
            torn[ends[0] + 4: ends[0] + 8] = struct.pack("<i", 60)
            checks["size_bytes under 61: not sound"] = scan(bytes(torn))[0] == 2
            for name, ok in checks.items():
                check("scan_internal_frames: " + name, ok)
        else:
            check("rp_scan_internal_frames symbol present", False)
    else:
        check("rp_frame_internal_many symbol present", False)

    print(("PASS" if failures == 0 else f"FAIL ({failures})"))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
