"""KV-block transfer: the TPU-native replacement for NIXL.

Reference model (docs/design-docs/kvbm-design.md:171-230, disagg-serving.md:
17-21): prefill and decode exchange *serialized layout metadata* plus the
block payload; the decode side owns the pull.  On GPU the payload moves
VRAM->VRAM over UCX/NVLink/IB.  Here the pull is RECEIVER-PACED and tiered
by deployment shape (the receiver picks the best available path):

  tier 1 — same process (engines sharing one JAX runtime, e.g. split
           sub-meshes of one slice): block chunks stay DEVICE-RESIDENT;
           the receiver `jax.device_put`s the sender's gathered chunk onto
           its own mesh sharding, so the bytes move over ICI without a
           host round-trip (disagg/broker.py).
  tier 2 — separate processes with the JAX transfer server available
           (jax.experimental.transfer, DCN cross-slice transfer): the
           request plane carries per-chunk METADATA (a uuid); the payload
           moves device-to-device through the transfer server
           (disagg/device_transfer.py).
  tier 3 — host-staged fallback, correct on any topology: chunks gather
           to host and ride the request plane as msgpack byte frames
           (RequestPlanePullSource below).

All tiers speak the same receiver-paced op protocol against the sender's
`kv_pull` endpoint:

  {"op": "open",  "request_id"}                  -> header frame
      header = {prompt_len, layout: KvLayout}    (+ "transfer_addr" when
      the sender runs a transfer server — tier-2 capability advertisement)
  {"op": "chunk", "request_id", "start", "count"[, "via": "transfer"]}
      -> one chunk frame: {"block_start", "block_count", "k", "v"} bytes
      (tier 3) or {"uuid": int} (tier 2 — pull the payload from the
      transfer server under that uuid)
  {"op": "close", "request_id"}                  -> {} (release parked KV)

Receiver pacing is what makes the pull STREAMING: each chunk is one
scheduler op on each engine, so decode bursts interleave with both the
sender's gathers and the receiver's injects, and neither side ever holds
more than one chunk of payload in host memory (the round-3 review called
out the whole-prompt triple materialization this replaces).

The logical layout contract is unchanged: payloads are logical blocks
[layers, n_blocks, block_size, kv_heads, head_dim] in the universal
transfer layout, gathered from whatever tp-sharding the prefill engine
used and re-sharded on inject by the decode engine's own GSPMD layout —
prefill TP != decode TP needs no special case (the reference calls this
out as a headline feature).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .. import obs

import ml_dtypes  # jax dependency; provides numpy bfloat16

_BF16 = np.dtype(ml_dtypes.bfloat16)

_DTYPES = {"float32": np.float32, "float16": np.float16,
           "int8": np.int8}

# Default chunk bound.  Well under the request plane's 256MB frame cap even
# after msgpack framing, large enough to amortize per-frame overhead.
DEFAULT_CHUNK_BYTES = 16 * 1024 * 1024


def _np_dtype(name: str):
    if name == "bfloat16":
        return _BF16
    return np.dtype(_DTYPES[name])


@dataclass
class KvLayout:
    """Logical geometry of a KV payload + the sender's parallel layout.

    The logical fields are contract: a mismatch is a model mismatch and the
    pull must fail.  The mesh fields are advisory (telemetry / transfer
    path negotiation) — resharding is the receiver's GSPMD's job, not the
    protocol's."""

    num_layers: int
    num_blocks: int
    block_size: int
    kv_heads: int
    head_dim: int
    dtype: str
    tp: int = 1
    dp: int = 1
    # MLA engines cache an asymmetric pair (latent R vs rope-key dr,
    # models/deepseek.py) — 0 means "v matches k" (the GQA case)
    head_dim_v: int = 0
    # int8-quantized payload (quant/kv.py): chunks carry fp32 scale
    # planes [L, n, bs, nkv] alongside k/v — the quantized representation
    # rides the wire verbatim (half the payload bytes, scales bit-exact)
    scales: bool = False

    @property
    def hd_v(self) -> int:
        return self.head_dim_v or self.head_dim

    def to_dict(self) -> Dict[str, Any]:
        return {
            "num_layers": self.num_layers, "num_blocks": self.num_blocks,
            "block_size": self.block_size, "kv_heads": self.kv_heads,
            "head_dim": self.head_dim, "dtype": self.dtype,
            "tp": self.tp, "dp": self.dp, "head_dim_v": self.head_dim_v,
            "scales": self.scales,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "KvLayout":
        return cls(**{k: d[k] for k in (
            "num_layers", "num_blocks", "block_size", "kv_heads",
            "head_dim", "dtype")}, tp=d.get("tp", 1), dp=d.get("dp", 1),
            head_dim_v=d.get("head_dim_v", 0),
            scales=bool(d.get("scales", False)))

    @classmethod
    def of(cls, k, tp: int = 1, dp: int = 1, v=None,
           scales: bool = False) -> "KvLayout":
        """From a universal-layout K (and optionally V) array."""
        L, nb, bs, nkv, hd = k.shape
        hd_v = v.shape[4] if v is not None and v.shape[4] != hd else 0
        return cls(num_layers=L, num_blocks=nb, block_size=bs, kv_heads=nkv,
                   head_dim=hd, dtype=np.dtype(k.dtype).name, tp=tp, dp=dp,
                   head_dim_v=hd_v, scales=scales)

    def check_compatible(self, other: "KvLayout") -> None:
        """Logical-geometry contract check (tp/dp intentionally excluded).
        `dtype`/`scales` are part of the contract: an int8 payload cannot
        scatter into a bf16 cache (or vice versa) without silent
        corruption — mixed-dtype disagg pairs must fail the pull (the
        decode side then falls back to local prefill)."""
        for f in ("num_layers", "block_size", "kv_heads", "head_dim",
                  "dtype", "scales"):
            a, b = getattr(self, f), getattr(other, f)
            if a != b:
                raise ValueError(
                    f"incompatible KV layout: {f} is {a} on the sender but "
                    f"{b} on the receiver"
                )
        if self.hd_v != other.hd_v:
            raise ValueError(
                f"incompatible KV layout: head_dim_v is {self.hd_v} on the "
                f"sender but {other.hd_v} on the receiver"
            )

    # -- chunk sizing -----------------------------------------------------
    def block_bytes(self) -> int:
        """Payload bytes of ONE block across all layers (k + v, plus the
        fp32 scale planes for a quantized payload)."""
        dt = _np_dtype(self.dtype)
        per_tok = self.kv_heads * (self.head_dim + self.hd_v)
        data = self.num_layers * self.block_size * per_tok * dt.itemsize
        if self.scales:
            data += self.num_layers * self.block_size * self.kv_heads * 2 * 4
        return data

    def blocks_per_chunk(self, max_bytes: int = DEFAULT_CHUNK_BYTES) -> int:
        """Whole blocks per chunk under the byte bound (always >= 1: the
        bound is a target; the request plane's frame cap is the hard
        limit)."""
        return max(1, max_bytes // max(1, self.block_bytes()))


def make_header(prompt_len: int, layout: KvLayout,
                transfer_addr: Optional[str] = None) -> Dict[str, Any]:
    h: Dict[str, Any] = {"prompt_len": prompt_len,
                         "layout": layout.to_dict()}
    if transfer_addr:
        h["transfer_addr"] = transfer_addr
    return h


def encode_chunk_frame(b0: int, kb: np.ndarray, vb: np.ndarray,
                       ksb: np.ndarray = None,
                       vsb: np.ndarray = None) -> Dict[str, Any]:
    """Host-staged chunk -> wire frame.  kb/vb are universal-layout
    [L, n, bs, nkv, hd] for the block range [b0, b0+n); a quantized
    payload adds the fp32 scale planes ksb/vsb [L, n, bs, nkv]."""
    frame = {
        "block_start": int(b0),
        "block_count": int(kb.shape[1]),
        "k": np.ascontiguousarray(kb).tobytes(),
        "v": np.ascontiguousarray(vb).tobytes(),
    }
    if ksb is not None:
        frame["ks"] = np.ascontiguousarray(ksb).tobytes()
        frame["vs"] = np.ascontiguousarray(vsb).tobytes()
    frame["crc"] = _frame_crc(frame)
    return frame


def _frame_crc(frame: Dict[str, Any]) -> int:
    """crc32 over the frame's payload byte members in canonical order,
    seeded with (block_start, block_count) so a frame spliced onto the
    wrong block range fails verification too."""
    import zlib

    crc = zlib.crc32(
        f"{int(frame['block_start'])}:{int(frame['block_count'])}"
        .encode())
    for name in ("k", "v", "ks", "vs"):
        if name in frame:
            crc = zlib.crc32(frame[name], crc)
    return crc & 0xFFFFFFFF


def decode_chunk_frame(
    frame: Dict[str, Any], layout: KvLayout
) -> Tuple[Any, ...]:
    """Wire frame -> (b0, n, kb, vb[, ksb, vsb]) with bounds checked
    against the header layout (a corrupt frame must not write outside the
    payload).  The scale planes come back only when the layout declares
    them — and a declaring layout REQUIRES them (a frame without scales
    for an int8 payload is corrupt)."""
    b0 = int(frame["block_start"])
    n = int(frame["block_count"])
    if not (0 <= b0 and n >= 1 and b0 + n <= layout.num_blocks):
        raise ValueError(f"chunk out of bounds: blocks=[{b0},{b0 + n}) of "
                         f"{layout.num_blocks}")
    if "crc" in frame and _frame_crc(frame) != int(frame["crc"]):
        # same failure family as every other malformed frame — the
        # caller's existing local-prefill fallback handles it (a frame
        # without a crc is an unupgraded sender and passes)
        raise ValueError(
            f"chunk frame for blocks [{b0},{b0 + n}) failed its crc32 "
            "footer")
    dt = _np_dtype(layout.dtype)
    lo = layout
    kb = np.frombuffer(frame["k"], dtype=dt).reshape(
        (lo.num_layers, n, lo.block_size, lo.kv_heads, lo.head_dim))
    vb = np.frombuffer(frame["v"], dtype=dt).reshape(
        (lo.num_layers, n, lo.block_size, lo.kv_heads, lo.hd_v))
    if not lo.scales:
        return b0, n, kb, vb
    if "ks" not in frame or "vs" not in frame:
        raise ValueError("quantized chunk frame is missing scale planes")
    sshape = (lo.num_layers, n, lo.block_size, lo.kv_heads)
    ksb = np.frombuffer(frame["ks"], dtype=np.float32).reshape(sshape)
    vsb = np.frombuffer(frame["vs"], dtype=np.float32).reshape(sshape)
    return b0, n, kb, vb, ksb, vsb


class PullSource:
    """Receiver-side pull driver interface (the engine paces it).

    open()  -> header dict ({"prompt_len", "layout", ...})
    chunk(b0, n) -> (kb, vb) — plus (ksb, vsb) scale planes for an int8
        payload — for blocks [b0, b0+n): numpy arrays (tier 3) or device
        arrays (tiers 1-2; the engine device_puts them onto its own
        sharding before injecting)
    close() -> release the sender's parked KV.  Idempotent; called on
        success AND failure."""

    async def open(self) -> Dict[str, Any]:
        raise NotImplementedError

    async def chunk(self, b0: int, n: int) -> Tuple[Any, ...]:
        raise NotImplementedError

    async def close(self) -> None:
        raise NotImplementedError


class RequestPlanePullSource(PullSource):
    """Tier 3: host-staged chunks over the request plane (the universal
    fallback).  One RPC per op; the sender gathers each chunk as its own
    scheduler op, so its decode interleaves with the extraction."""

    def __init__(self, client, params: Dict[str, Any]):
        self.client = client
        self.params = params
        self.layout: Optional[KvLayout] = None

    async def _call(self, body: Dict[str, Any]) -> Dict[str, Any]:
        out = None
        async for item in self.client.generate(
            body, instance_id=self.params["instance_id"]
        ):
            out = item
        if out is None:
            raise RuntimeError("empty kv_pull response")
        return out

    async def open(self) -> Dict[str, Any]:
        with obs.span("disagg_open",
                      request_id=self.params["request_id"]):
            header = await self._call(
                {"op": "open", "request_id": self.params["request_id"]})
        self.layout = KvLayout.from_dict(header["layout"])
        return header

    async def chunk(self, b0: int, n: int):
        with obs.span("disagg_chunk",
                      request_id=self.params["request_id"],
                      start=int(b0), count=int(n)):
            frame = await self._call({
                "op": "chunk", "request_id": self.params["request_id"],
                "start": int(b0), "count": int(n),
            })
        out = decode_chunk_frame(frame, self.layout)
        fb0, fn, arrs = out[0], out[1], out[2:]
        if fb0 != b0 or fn != n:
            raise ValueError(f"sender returned blocks [{fb0},{fb0 + fn}) "
                             f"for a request of [{b0},{b0 + n})")
        return arrs

    async def close(self) -> None:
        try:
            await self._call({"op": "close",
                              "request_id": self.params["request_id"]})
        except Exception:
            pass  # sender-side TTL reaps unreleased parks


def make_transfer_params(
    *,
    instance_id: int,
    request_id: str,
    prompt_len: int,
    first_token: int,
    block_size: int,
    num_layers: int,
    engine: str = "jax",
) -> Dict[str, Any]:
    """kv_transfer_params attached to the prefill response (the analogue of
    vLLM's NIXL block-id metadata / TRT-LLM's opaque_state,
    disagg-serving.md:53-61)."""
    return {
        "engine": engine,
        "instance_id": instance_id,
        "request_id": request_id,
        "prompt_len": prompt_len,
        "first_token": first_token,
        "block_size": block_size,
        "num_layers": num_layers,
    }
