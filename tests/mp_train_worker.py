"""Worker script for the multi-process e2e launcher test.

Each of the N processes (1 fake CPU device each) initializes horovod_tpu
from the launcher-injected env, then exercises the negotiated collective
path — the whole reference flow of †3.4 (launch) + †3.2 (hot path): async
enqueue → coordinator negotiation → identical fused dispatch on every
process → synchronize.
"""

import os
import sys

# One device per process = one rank per process (the reference's model).
# The platform is the launcher's (`hvdrun --platform`, read by hvd.init);
# on the CPU rig each process gets exactly one host device.
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=1"

import numpy as np  # noqa: E402
import horovod_tpu as hvd  # noqa: E402


def main() -> int:
    # Per-rank Chrome-trace timeline; phases self-checked below
    # († timeline.cc QUEUE/NEGOTIATE/DISPATCH breakdown over a real
    # multi-process negotiation).
    import tempfile
    tl_fd, tl_path = tempfile.mkstemp(
        prefix=f"hvdtpu_tl_r{os.environ.get('HVDTPU_CROSS_RANK', '0')}_",
        suffix=".json")
    os.close(tl_fd)
    os.environ["HOROVOD_TIMELINE"] = tl_path
    hvd.init()
    me = hvd.cross_rank()
    n = hvd.size()
    assert hvd.cross_size() == n, (hvd.cross_size(), n)

    # 1. negotiated sync allreduce
    x = hvd.from_local(np.full((1, 4), float(me + 1), np.float32))
    out = hvd.to_numpy(hvd.allreduce(x, hvd.Sum))
    expected = sum(range(1, n + 1))
    assert np.allclose(out, expected), (out, expected)

    # 2. async + fusion across the negotiated path
    hs = [hvd.allreduce_async(
        hvd.from_local(np.full((1, 3), float(me + i), np.float32)),
        hvd.Average, name=f"grad.{i}") for i in range(5)]
    for i, h in enumerate(hs):
        got = hvd.to_numpy(hvd.synchronize(h))
        want = np.mean([r + i for r in range(n)])
        assert np.allclose(got, want), (i, got, want)

    # 3. broadcast from rank 1
    b = hvd.to_numpy(hvd.broadcast(
        hvd.from_local(np.full((1, 2), float(me), np.float32)), 1))
    assert np.allclose(b, 1.0), b

    # 4. barrier
    hvd.barrier()

    # 5. ragged allgather († MPI_Allgatherv): unequal row counts per rank,
    # composed from negotiated uniform collectives (pad-to-max + slice).
    rows = 2 + 3 * me
    piece = (np.arange(rows * 2, dtype=np.float32).reshape(rows, 2)
             + 100.0 * me)
    got = hvd.to_numpy(hvd.allgather([piece]))
    expected = np.concatenate([
        np.arange((2 + 3 * r) * 2, dtype=np.float32).reshape(-1, 2) + 100.0 * r
        for r in range(n)])
    assert got.shape == expected.shape, (got.shape, expected.shape)
    assert np.allclose(got, expected), (me, got, expected)

    # 6. non-uniform alltoall († MPI_Alltoallv): per-rank splits differ.
    # Works at any np: source i sends 1 + ((i + j) % 2) rows to rank j.
    def splits_of(i):
        return [1 + ((i + j) % 2) for j in range(n)]

    my_splits = splits_of(me)
    send = np.arange(sum(my_splits), dtype=np.float32) + 10.0 * me
    recv = hvd.alltoall([send], splits=np.array([my_splits], np.int32))
    # rank r receives splits_i[r] rows from each source i, source-ordered,
    # each source's rows starting at sum(splits_i[:r]) of its send buffer.
    want_parts = []
    for i in range(n):
        sp = splits_of(i)
        start = sum(sp[:me])
        want_parts.append(
            np.arange(start, start + sp[me], dtype=np.float32) + 10.0 * i)
    want = np.concatenate(want_parts)
    got_a2a = hvd.to_numpy(recv[0])
    assert np.allclose(got_a2a, want), (me, got_a2a, want)

    hvd.shutdown()

    import json
    from horovod_tpu.utils.timeline import rank_suffixed
    events = json.load(open(rank_suffixed(tl_path, me, n)))
    spans = [e["name"] for e in events if e.get("ph") == "B"]
    for phase in ("QUEUE", "NEGOTIATE", "DISPATCH"):
        assert phase in spans, f"timeline missing {phase}: {spans[:20]}"

    print(f"rank {me}: OK sum={float(out[0])}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
