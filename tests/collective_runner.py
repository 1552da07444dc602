"""Worker script for multi-process collective tests (spawned by the launch
CLI; the reference pattern is test/legacy_test/test_collective_api_base.py
runner scripts under test/collective/).

Each rank builds deterministic per-rank values, runs the eager collective
API across real processes, checks against the numpy oracle, and appends
"ok <name>" lines to $COLLECTIVE_OUT.<rank>.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402
from paddle_tpu.distributed.collective import ReduceOp  # noqa: E402


def main():
    dist.init_parallel_env()
    rank = dist.get_rank()
    world = dist.get_world_size()
    assert world > 1, "runner requires the multi-process regime"
    out_path = os.environ["COLLECTIVE_OUT"] + f".{rank}"
    results = []

    def record(name, ok):
        results.append(f"{'ok' if ok else 'FAIL'} {name}")
        if not ok:
            print(f"[rank {rank}] FAIL {name}", flush=True)

    base = [np.arange(8, dtype=np.float32) + 10 * r for r in range(world)]

    # all_reduce
    t = paddle.to_tensor(base[rank].copy())
    dist.all_reduce(t)
    record("all_reduce_sum", np.allclose(t.numpy(), sum(base)))
    t = paddle.to_tensor(base[rank].copy())
    dist.all_reduce(t, op=ReduceOp.MAX)
    record("all_reduce_max", np.allclose(t.numpy(), np.max(base, axis=0)))

    # all_gather
    got = []
    dist.all_gather(got, paddle.to_tensor(base[rank].copy()))
    ok = len(got) == world and all(
        np.allclose(g.numpy(), base[r]) for r, g in enumerate(got))
    record("all_gather", ok)

    # reduce_scatter: input [world*2], each rank keeps its 2-chunk of the sum
    ins = [np.arange(world * 2, dtype=np.float32) * (r + 1)
           for r in range(world)]
    dst = paddle.to_tensor(np.zeros(2, np.float32))
    dist.reduce_scatter(dst, paddle.to_tensor(ins[rank].copy()))
    want = sum(ins)[rank * 2:(rank + 1) * 2]
    record("reduce_scatter", np.allclose(dst.numpy(), want))

    # broadcast
    t = paddle.to_tensor(base[rank].copy())
    dist.broadcast(t, src=1)
    record("broadcast", np.allclose(t.numpy(), base[1]))

    # all_to_all: rank r sends chunk j to rank j
    chunks = [paddle.to_tensor(np.full(3, 100 * rank + j, np.float32))
              for j in range(world)]
    outs = []
    dist.all_to_all(outs, chunks)
    ok = all(np.allclose(outs[j].numpy(), np.full(3, 100 * j + rank))
             for j in range(world))
    record("all_to_all", ok)

    # scatter from rank 0
    lst = ([paddle.to_tensor(np.full(4, 7.0 + r, np.float32))
            for r in range(world)] if rank == 0 else None)
    t = paddle.to_tensor(np.zeros(4, np.float32))
    dist.scatter(t, lst, src=0)
    record("scatter", np.allclose(t.numpy(), np.full(4, 7.0 + rank)))

    # p2p: 0 -> 1
    if rank == 0:
        dist.send(paddle.to_tensor(np.full(5, 42.0, np.float32)), dst=1)
        record("send", True)
    elif rank == 1:
        t = paddle.to_tensor(np.zeros(5, np.float32))
        dist.recv(t, src=0)
        record("recv", np.allclose(t.numpy(), 42.0))

    # object gather
    objs = []
    dist.all_gather_object(objs, {"rank": rank, "tag": "x" * (rank + 1)})
    record("all_gather_object",
           objs == [{"rank": r, "tag": "x" * (r + 1)} for r in range(world)])

    # strict-subgroup collectives: ONLY members enter the call (true
    # ProcessGroup semantics) while the other ranks do unrelated work
    sub = dist.new_group(ranks=[0, 1])
    if rank in (0, 1):
        t = paddle.to_tensor(base[rank].copy())
        dist.all_reduce(t, group=sub)
        record("subgroup_all_reduce",
               np.allclose(t.numpy(), base[0] + base[1]))
        t = paddle.to_tensor(base[rank].copy())
        dist.broadcast(t, src=1, group=sub)
        record("subgroup_broadcast", np.allclose(t.numpy(), base[1]))
        # rotating src across >2 rounds exercises the GC path where round
        # seq-2's src differs from the current src
        ok = True
        for i, s in enumerate([0, 1, 0, 1, 0]):
            t = paddle.to_tensor(base[rank] + float(i))
            dist.broadcast(t, src=s, group=sub)
            ok = ok and np.allclose(t.numpy(), base[s] + float(i))
        record("subgroup_broadcast_rotating_src", ok)
        got = []
        dist.all_gather(got, paddle.to_tensor(base[rank].copy()), group=sub)
        record("subgroup_all_gather", len(got) == 2 and
               np.allclose(got[0].numpy(), base[0]) and
               np.allclose(got[1].numpy(), base[1]))
        dist.barrier(group=sub)
        record("subgroup_barrier", True)
    else:
        # non-member calling the collective: warn + no-op, value unchanged
        # (reference _warn_cur_rank_not_in_group semantics)
        t = paddle.to_tensor(base[rank].copy())
        dist.all_reduce(t, group=sub)
        record("subgroup_nonmember_noop", np.allclose(t.numpy(), base[rank]))

    # batched async P2P: symmetric exchange via batch_isend_irecv
    # (reference: communication/batch_isend_irecv.py) — rank0 <-> rank1
    if rank in (0, 1) and world >= 2:
        peer = 1 - rank
        mine = paddle.to_tensor(np.full(3, 10.0 + rank, np.float32))
        theirs = paddle.to_tensor(np.zeros(3, np.float32))
        ops = [dist.P2POp(dist.isend, mine, peer),
               dist.P2POp(dist.irecv, theirs, peer)]
        for t_ in dist.batch_isend_irecv(ops):
            t_.wait()
        record("batch_isend_irecv",
               np.allclose(theirs.numpy(), np.full(3, 10.0 + peer)))

    # all_to_all_single is a COLLECTIVE: every rank participates
    rows = 2 * world
    src = paddle.to_tensor(
        np.arange(rows, dtype=np.float32) + 100 * rank)
    dst = paddle.to_tensor(np.zeros(rows, np.float32))
    dist.all_to_all_single(dst, src)
    want = np.concatenate([
        (np.arange(rows, dtype=np.float32) + 100 * r)[
            rank * 2:(rank + 1) * 2] for r in range(world)])
    record("all_to_all_single", np.allclose(dst.numpy(), want))

    dist.barrier()
    with open(out_path, "w") as f:
        f.write("\n".join(results) + "\n")
    if any(r.startswith("FAIL") for r in results):
        sys.exit(1)


if __name__ == "__main__":
    main()
