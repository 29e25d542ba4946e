"""Raw gather-DMA microbench: what bandwidth can a Pallas kernel actually
pull from HBM for paged-KV gathers, per cache layout?

Decides the round-5 layout question: the decode kernel's DMA leg measures
~190 GB/s on the head-major layout ([nkv, nb, hd, bs] — a block's planes
are 8 strided 32KB runs), far under the 819 GB/s pin.  Candidates:

  strided     current: one descriptor per block, [nkv, hd, bs] with a
              ~4.6 MB stride between 32KB head planes
  contig      block-major layout ([nb, nkv, hd, bs]): one contiguous
              256KB descriptor per block
  seq         sequential whole-slab read via BlockSpec pipelining
              (no gather at all — upper bound)

Prints GB/s for each.  Run: python benchmarks/bench_dma_layouts.py
"""

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NKV, HD, BS = 8, 128, 128
NB = 1024            # pool blocks (256 MB slab at bf16)
NREAD = 512          # blocks gathered per kernel call (128 MB)
BPC = 8              # blocks per chunk
HBM_GBPS = 0.0       # the chip's published HBM peak, looked up in main()


def _sync(r):
    np.asarray(jax.device_get(r.ravel()[0]))


def timeit(fn, n=6, warm=2):
    for _ in range(warm):
        r = fn()
    _sync(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    _sync(r)
    return (time.perf_counter() - t0) / n


REPS = 8  # in-kernel repeats: amortize the fixed per-dispatch cost


def gather_kernel(tables_ref, hbm, o_ref, buf, sem, *, mode, nread):
    n_chunks = nread // BPC

    def start(c, slot):
        for i in range(BPC):
            pid = tables_ref[c * BPC + i]
            if mode == "strided":
                cp = pltpu.make_async_copy(
                    hbm.at[:, pid], buf.at[slot, i], sem.at[slot])
            else:
                cp = pltpu.make_async_copy(
                    hbm.at[pid], buf.at[slot, i], sem.at[slot])
            cp.start()

    def wait(c, slot):
        for i in range(BPC):
            pid = tables_ref[c * BPC + i]
            if mode == "strided":
                cp = pltpu.make_async_copy(
                    hbm.at[:, pid], buf.at[slot, i], sem.at[slot])
            else:
                cp = pltpu.make_async_copy(
                    hbm.at[pid], buf.at[slot, i], sem.at[slot])
            cp.wait()

    start(0, 0)
    acc0 = jnp.zeros((8, 128), jnp.float32)

    def body(t, acc):
        c = jax.lax.rem(t, n_chunks)
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < REPS * n_chunks)
        def _():
            start(jax.lax.rem(t + 1, n_chunks), jax.lax.rem(t + 1, 2))
        wait(c, slot)
        return acc + buf[slot, 0, 0, :8, :].astype(jnp.float32)

    acc = jax.lax.fori_loop(0, REPS * n_chunks, body, acc0)
    o_ref[...] = acc


def make_gather(mode):
    buf = pltpu.VMEM((2, BPC, NKV, HD, BS), jnp.bfloat16)
    fn = pl.pallas_call(
        functools.partial(gather_kernel, mode=mode, nread=NREAD),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((8, 128), lambda i, *r: (0, 0)),
            scratch_shapes=[buf, pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )
    return jax.jit(fn)


def main():
    global HBM_GBPS
    from dynamo_tpu.runtime.device import device_peaks, require_tpu

    device = require_tpu()  # compiled DMA kernels: no off-chip mode
    HBM_GBPS = device_peaks(device["kind"])["hbm_gbps"]
    print(f"device: {device} pin {HBM_GBPS:.0f} GB/s")
    rng = np.random.default_rng(0)
    tables = jnp.asarray(rng.permutation(NB)[:NREAD].astype(np.int32))
    nbytes = NREAD * NKV * HD * BS * 2 * REPS
    print(f"gather payload: {nbytes/1e6:.0f} MB "
          f"({REPS}x{NREAD} blocks) per call")

    hbm_hm = jnp.zeros((NKV, NB, HD, BS), jnp.bfloat16)   # head-major
    g = make_gather("strided")
    t = timeit(lambda: g(tables, hbm_hm))
    print(f"  strided (head-major):  {nbytes/t/1e9:6.1f} GB/s "
          f"({nbytes/t/1e9/HBM_GBPS*100:4.1f}% of pin)")
    del hbm_hm

    hbm_bm = jnp.zeros((NB, NKV, HD, BS), jnp.bfloat16)   # block-major
    g = make_gather("contig")
    t = timeit(lambda: g(tables, hbm_bm))
    print(f"  contig (block-major):  {nbytes/t/1e9:6.1f} GB/s "
          f"({nbytes/t/1e9/HBM_GBPS*100:4.1f}% of pin)")

    # sequential upper bound: stream the whole slab through BlockSpec
    # pipelining and reduce it
    def seq_kernel(x_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)
        o_ref[...] += x_ref[0, 0].astype(jnp.float32)

    seq = pl.pallas_call(
        seq_kernel,
        grid=(REPS * NB // BPC,),
        in_specs=[pl.BlockSpec(
            (BPC, NKV, HD, BS),
            lambda i: (jax.lax.rem(i, NB // BPC), 0, 0, 0))],
        out_specs=pl.BlockSpec((HD, BS), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((HD, BS), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )
    seq = jax.jit(seq)
    seq_bytes = REPS * NB * NKV * HD * BS * 2
    t = timeit(lambda: seq(hbm_bm))
    print(f"  sequential pipeline:   {seq_bytes/t/1e9:6.1f} GB/s "
          f"({seq_bytes/t/1e9/HBM_GBPS*100:4.1f}% of pin)")


if __name__ == "__main__":
    import argparse

    argparse.ArgumentParser(
        description="raw gather-DMA layout microbench (no options; "
                    "requires a TPU)").parse_args()
    main()
