"""KV migration payloads: block-shaped cache rows packed for the replica wire.

Prefill/decode disaggregation ships a sequence's KV blocks from the
prefill replica that computed them to the decode replica that will own
the sequence.  This module is the wire format — the pure function pair
``pack_kv`` / ``unpack_kv`` between the engine's block-granular export
(``kv_cache.export_blocks``, per part and layer ``(n, bs, *row)``, the
parts and their rows being the model's pool layout: K and V of ``(H,
Dh)``, or one latent ``ckv`` row) and bytes:

- **codec per hop, reusing ``ops/quantize.py``** (EQuARX's move applied
  to the migration hop instead of the allreduce hop): ``f32`` ships the
  pool bytes verbatim — ``np.float32`` tobytes/frombuffer is bitwise, so
  an f32 migration is provably byte-identical to a local prefill and the
  greedy decode stays bitwise against the colocated engine.  ``int8``
  ships block-scaled 8-bit at ~4x less wire, with per-element error
  bounded by ``Codec.error_bound(amax, 1, widths=(1,))`` = ``amax/127``
  for the single migration hop (one encode, one decode, no accumulation)
  — ``tests/test_disagg.py`` checks both the bound and greedy token
  identity against the oracle.
- **refuse, don't guess**: the decode side verifies the whole-payload
  CRC, every per-tensor CRC, the declared geometry against its OWN model
  config, and the byte counts before a single element lands in its pool.
  Any mismatch raises :class:`MigrationError` (``FT_MIGRATION_REFUSED``)
  and the payload is dropped — admitting a corrupt or mis-shaped KV
  would silently poison one sequence's attention, the exact failure
  class the CRC-trailered RPC framing exists to make loud.

Tensor order on the wire is fixed (layer-major, the parts in the order
the payload's ``layout`` states them: K before V) and the meta states the
layout, which the receiver holds against its own model's.  A model whose
layers keep something a SLOT and not a position (a recurrent state:
``models.configs.slot_parts``) ships it behind the rows, in the same blob,
under the same codec and CRCs, stated under ``meta["state"]``
(:func:`unpack_state`).  The meta dict travels in the
RPC JSON body, the blob rides base64-chunked frames (``rpc.chunk_blob``).
"""

from __future__ import annotations

import zlib

import numpy as np

from ..ops.quantize import decode_int8, encode_int8, get_codec

__all__ = [
    "MigrationError",
    "pack_kv",
    "unpack_kv",
    "unpack_state",
    "migration_error_bound",
]


class MigrationError(RuntimeError):
    """A migration payload failed verification (or packing hit an
    unsupported codec) — the decode side refuses the handoff and the
    prefill side falls back to releasing its export.  Stable-code'd like
    the other loud serving failures."""

    code = "FT_MIGRATION_REFUSED"

    def __init__(self, msg: str):
        super().__init__(f"{self.code}: {msg}")


def _crc(data: bytes) -> int:
    return zlib.crc32(data) & 0xFFFFFFFF


def _tensors(kv: dict):
    """Fixed wire order: layer-major, the parts in ``kv``'s order."""
    for layer in range(len(next(iter(kv.values())))):
        for part, layers in kv.items():
            yield layer, part, layers[layer]


def _encode(tree: dict, lead: tuple, c) -> tuple:
    """``(layout, entries, payloads)`` of ``{part: [array a layer]}``
    whose arrays are ``(*lead, *layout[part])``."""
    layout = {
        part: [int(x) for x in np.asarray(layers[0]).shape[len(lead):]]
        for part, layers in tree.items()
    }
    entries, payloads = [], []
    for layer, part, arr in _tensors(tree):
        a = np.ascontiguousarray(np.asarray(arr, dtype=np.float32))
        if a.shape != (*lead, *layout[part]):
            raise MigrationError(
                f"layer {layer} {part} shaped {a.shape}, expected "
                f"{(*lead, *layout[part])}"
            )
        if c.name == "f32":
            payload = a.tobytes()
            entry = {"layer": layer, "part": part, "nbytes": len(payload)}
        else:
            flat = a.reshape(-1)
            q, scales = encode_int8(flat, 0, salt=0, block=c.block)
            qb = np.asarray(q, np.int8).tobytes()
            sb = np.ascontiguousarray(np.asarray(scales, np.float32)).tobytes()
            payload = qb + sb
            entry = {
                "layer": layer,
                "part": part,
                "nbytes": len(payload),
                "nbytes_q": len(qb),
                "length": int(flat.shape[0]),
                "amax": float(np.max(np.abs(flat))) if flat.size else 0.0,
            }
        entry["crc32"] = _crc(payload)
        entries.append(entry)
        payloads.append(payload)
    return layout, entries, payloads


def pack_kv(kv: dict, *, codec: str = "f32",
            state: dict | None = None) -> tuple[dict, bytes]:
    """Pack block-shaped cache rows into ``(meta, blob)`` for the wire.

    ``kv`` is ``export_blocks`` output: per part and layer ``(n, bs,
    *row)``.  ``meta`` declares the geometry (``layout``: each part's
    row shape), codec, and per-tensor byte spans + CRCs; ``blob`` is the
    concatenated tensor payload in fixed order.
    The f32 codec emits each tensor's float32 bytes verbatim (bitwise);
    int8 emits ``encode_int8``'s (q, scales) pair per tensor, flattened,
    with the tensor's amax recorded so the receiver can state the
    documented error bound without re-deriving it.

    ``state``: what the sequence carries a SLOT and not a position
    (``{part: [array a layer that holds it]}``, a recurrent layer's state;
    ``models.configs.slot_parts``), shipped behind the rows in the same
    blob and stated under ``meta["state"]`` (its own ``layout``,
    ``n_layers``, ``nbytes`` and ``tensors``); None or empty for a model
    that keeps nothing a slot, whose ``meta`` then has no such key.
    """
    c = get_codec(codec)
    if c.name not in ("f32", "int8"):
        raise MigrationError(
            f"codec {c.name!r} is not a migration codec (f32 | int8)"
        )
    n, bs = np.asarray(next(iter(kv.values()))[0]).shape[:2]
    layout, tensors, parts = _encode(kv, (int(n), int(bs)), c)
    meta = {
        "codec": c.name,
        "codec_block": c.block,
        "n_blocks": int(n),
        "block_size": int(bs),
        "layout": layout,
        "n_layers": len(next(iter(kv.values()))),
        "tensors": tensors,
    }
    if state:
        s_layout, s_tensors, s_parts = _encode(state, (), c)
        meta["state"] = {
            "layout": s_layout,
            "n_layers": len(next(iter(state.values()))),
            "nbytes": sum(len(p) for p in s_parts),
            "tensors": s_tensors,
        }
        parts += s_parts
    blob = b"".join(parts)
    meta["nbytes"] = len(blob)
    meta["crc32"] = _crc(blob)
    return meta, blob


def _decode(tensors, layout: dict, layers: int, lead: tuple, codec,
            blob: bytes) -> dict:
    """The tensors ``_encode`` wrote, verified one by one: ``{part: [np
    (*lead, *layout[part]) f32 a layer]}``; ``blob`` holds exactly them."""
    if len(tensors) != len(layout) * layers:
        raise MigrationError(
            f"{len(tensors)} tensors declared for {layers} layers "
            f"(expected {len(layout) * layers})"
        )
    out = {part: [None] * layers for part in layout}
    off = 0
    for i, entry in enumerate(tensors):
        try:
            layer, part = int(entry["layer"]), str(entry["part"])
            nbytes, crc = int(entry["nbytes"]), int(entry["crc32"])
        except (KeyError, TypeError, ValueError) as e:
            raise MigrationError(f"malformed tensor entry {i}: {e}") from None
        if not (0 <= layer < layers and part in layout):
            raise MigrationError(f"tensor entry {i} addresses {part}@{layer}")
        if out[part][layer] is not None:
            raise MigrationError(f"duplicate tensor {part}@{layer}")
        shape = (*lead, *layout[part])
        count = int(np.prod(shape))
        payload = blob[off : off + nbytes]
        if len(payload) != nbytes:
            raise MigrationError(
                f"tensor {part}@{layer} truncated: {len(payload)}/{nbytes} bytes"
            )
        off += nbytes
        if _crc(payload) != crc:
            raise MigrationError(f"tensor {part}@{layer} CRC mismatch")
        if codec.name == "f32":
            if nbytes != count * 4:
                raise MigrationError(
                    f"tensor {part}@{layer} is {nbytes} bytes, shape "
                    f"{shape} needs {count * 4}"
                )
            arr = np.frombuffer(payload, np.float32).reshape(shape)
        else:
            try:
                nbytes_q = int(entry["nbytes_q"])
                length = int(entry["length"])
            except (KeyError, TypeError, ValueError) as e:
                raise MigrationError(
                    f"malformed int8 tensor entry {i}: {e}"
                ) from None
            blk = codec.block
            padded = -(-length // blk) * blk
            if length != count or nbytes_q != padded:
                raise MigrationError(
                    f"tensor {part}@{layer} int8 geometry drift: length "
                    f"{length} (want {count}), q bytes {nbytes_q} (want {padded})"
                )
            if nbytes != nbytes_q + (padded // blk) * 4:
                raise MigrationError(
                    f"tensor {part}@{layer} is {nbytes} bytes, int8 + "
                    f"scales need {nbytes_q + (padded // blk) * 4}"
                )
            q = np.frombuffer(payload[:nbytes_q], np.int8)
            scales = np.frombuffer(payload[nbytes_q:], np.float32)
            arr = np.asarray(
                decode_int8(q, scales, length, block=blk), np.float32
            ).reshape(shape)
        out[part][layer] = arr
    if off != len(blob):
        raise MigrationError(
            f"{len(blob) - off} trailing bytes after the declared tensors"
        )
    return out


def _checked(meta: dict, blob: bytes) -> tuple:
    """The payload whole: ``(codec, bytes of the rows' tensors)`` once the
    blob's length and CRC are the meta's."""
    try:
        codec = get_codec(meta["codec"])
        state_bytes = int((meta.get("state") or {}).get("nbytes", 0))
    except (KeyError, TypeError, ValueError, AttributeError) as e:
        raise MigrationError(f"malformed migration meta: {e}") from None
    if len(blob) != int(meta.get("nbytes", -1)):
        raise MigrationError(
            f"payload is {len(blob)} bytes, meta declares {meta.get('nbytes')}"
        )
    if _crc(blob) != int(meta.get("crc32", -1)):
        raise MigrationError("payload CRC mismatch — corrupt migration blob")
    if not 0 <= state_bytes <= len(blob):
        raise MigrationError(f"state of {state_bytes} bytes in a {len(blob)}-byte payload")
    return codec, len(blob) - state_bytes


def _geometry(stated: dict) -> tuple:
    try:
        layout = {
            str(part): tuple(int(x) for x in row)
            for part, row in stated["layout"].items()
        }
        return layout, int(stated["n_layers"]), list(stated["tensors"])
    except (KeyError, TypeError, ValueError, AttributeError) as e:
        raise MigrationError(f"malformed migration meta: {e}") from None


def unpack_kv(meta: dict, blob: bytes) -> dict:
    """Verify and decode a migration payload back to block-shaped rows.

    Refuses loudly (:class:`MigrationError`) on: whole-blob CRC or byte
    count drift, per-tensor CRC drift, tensor count vs declared layers
    and parts, byte spans that do not reconstruct the declared geometry,
    unknown codec.  On success returns ``{part: [np (n, bs, *row) f32 a
    layer]}`` ready for ``kv_cache.write_imported``.  What the payload
    carries a slot is :func:`unpack_state`'s.
    """
    codec, rows_bytes = _checked(meta, blob)
    layout, layers, tensors = _geometry(meta)
    try:
        lead = (int(meta["n_blocks"]), int(meta["block_size"]))
    except (KeyError, TypeError, ValueError) as e:
        raise MigrationError(f"malformed migration meta: {e}") from None
    return _decode(tensors, layout, layers, lead, codec, blob[:rows_bytes])


def unpack_state(meta: dict, blob: bytes) -> dict:
    """The slot parts of a payload, verified as :func:`unpack_kv` verifies
    the rows: ``{part: [np (*shape) f32 a layer that holds it]}`` ready
    for ``kv_cache.write_state``; ``{}`` where the payload states none."""
    if not meta.get("state"):
        return {}
    codec, rows_bytes = _checked(meta, blob)
    layout, layers, tensors = _geometry(meta["state"])
    return _decode(tensors, layout, layers, (), codec, blob[rows_bytes:])


def migration_error_bound(meta: dict) -> float:
    """The documented per-element absolute error bound of one unpacked
    payload: 0 for f32, ``max(amax)/127`` across tensors for int8 — one
    migration hop is one encode + one decode with no accumulation, i.e.
    ``Codec.error_bound(amax, n=1, widths=(1,))``.  The disagg bench
    machine-checks decoded values against this."""
    codec = get_codec(meta["codec"])
    if not codec.lossy:
        return 0.0
    amax = max(
        (float(t.get("amax", 0.0)) for t in meta.get("tensors", ())),
        default=0.0,
    )
    return codec.error_bound(amax, 1, widths=(1,))
