"""Packed serving artifact — the calibration→serving hand-off format.

The RSQ pipeline's sharded write-back emits, per quantized weight, the
*packed* int codes (``quantizer.pack_codes``) plus per-group ``(scale,
zero)`` — 4-8x smaller than the fp tensor.  This module persists and
reloads that artifact:

  * **save** — every jax leaf is written one *addressable shard at a time*
    (``Array.addressable_shards``): on a d_out-sharded code tensor the host
    never copies more than one shard's data at once into the write buffer.
    The only routine that pulls a *full* array to the host is
    :func:`_host_gather`; the sharded write-back path never calls it (the
    regression test for the pipeline asserts exactly this), it exists for
    the legacy host-gather baseline and for plain-numpy leaves.  The
    on-disk artifact is one npz written by the single controller; true
    multi-host pods need per-host shard files (one npz per host, merged
    index in meta.json) — a recorded ROADMAP item, not yet built.
  * **load** — shards are reassembled on host *in packed form* (host memory
    only ever holds compressed bytes + the small group params) and the fp
    weight first exists on device, via ``quantizer.dequantize_packed``
    inside :func:`load_packed_params` — or **never**, via
    :func:`load_packed_forward_params`, which rebuilds the serving param
    tree with every quantized matrix as a ``PackedWeight`` pytree node:
    the codes stay packed in HBM and the model's ``linear`` dispatcher
    feeds them straight to the ``quant_matmul`` kernel.

Durability (format v3)
----------------------
Artifacts are *atomic and verified*: every file is written to a temp path
and ``os.replace``'d into place (a crash mid-save never leaves a truncated
artifact where a loader could find it), the npz payloads are written with
canonical zip metadata (fixed timestamps, stored entries) so two runs that
produce the same arrays produce **byte-identical files** — the contract the
kill-and-resume parity tests pin — and ``meta.json`` records the SHA-256 of
each payload file.  Loaders verify the checksum before deserializing and
raise :class:`ArtifactCorruptError` with an actionable message on mismatch
(``verify=False`` / ``launch.serve --no-verify`` opts out); v2/v1 artifacts
predate the checksum contract and load unverified.

On-disk layout (``<dir>/``):

  meta.json     — format tag, quant spec, per-file sha256 checksums,
                  per-entry metadata (d_in, group_size, dtype, layer
                  location) and the shard index map of every saved field —
                  packed *and* residual
  packed.npz    — ``"<entry>/<field>@<k>"`` -> the k-th shard's local data
  residual.npz  — the unquantized remainder of the param tree (norms,
                  routers, embeddings, ...) with quantized leaves replaced
                  by empty markers; written per addressable shard exactly
                  like the packed leaves (``"leaf_<i>@<k>"`` + shard index
                  in meta.json), so a d_out/vocab-sharded residual leaf
                  never gathers on the controller; treedef pickled in
                  meta.json
"""
from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import zipfile
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantizer import dequantize_packed
from repro.kernels.quant_matmul.ops import PackedWeight
from repro.runtime.sharding import LOCAL, ParallelCtx

FORMAT = "rsq-packed-v3"  # v3: per-file sha256 checksums + atomic,
# byte-deterministic writes (v2: shard-indexed residual; v1: whole-leaf)
_READABLE = (FORMAT, "rsq-packed-v2", "rsq-packed-v1")
_FIELDS = ("codes", "scale", "zero")


class ArtifactCorruptError(RuntimeError):
    """A packed artifact file failed its recorded SHA-256 check."""


def _sha256_file(path: Path) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _savez_atomic(path: Path, arrays: dict) -> str:
    """Write ``arrays`` as an npz at ``path`` atomically (temp file +
    ``os.replace``) and *canonically*: fixed zip timestamps and stored
    (uncompressed) members, so identical arrays written in identical
    order produce byte-identical files — ``np.savez`` stamps the current
    time into each zip header, which would break the resumed-vs-
    uninterrupted byte-parity contract.  Members are serialized one at a
    time (like ``np.savez``), so host memory still holds at most one
    shard's bytes beyond the write buffer.  Returns the file's sha256."""
    from numpy.lib import format as npformat

    tmp = path.with_suffix(path.suffix + ".tmp")
    with zipfile.ZipFile(tmp, "w", zipfile.ZIP_STORED,
                         allowZip64=True) as zf:
        for name, arr in arrays.items():
            buf = io.BytesIO()
            npformat.write_array(buf, np.asarray(arr), allow_pickle=False)
            zi = zipfile.ZipInfo(name + ".npy",
                                 date_time=(1980, 1, 1, 0, 0, 0))
            zi.compress_type = zipfile.ZIP_STORED
            zi.external_attr = 0o600 << 16
            zf.writestr(zi, buf.getvalue())
    sha = _sha256_file(tmp)
    os.replace(tmp, path)
    return sha


def _verify_file(d: Path, meta: dict, fname: str) -> None:
    """Check ``fname`` against the checksum recorded in ``meta``.

    Only v3 artifacts carry the checksum contract; earlier formats load
    unverified (they predate it)."""
    checksums = meta.get("checksums")
    if meta.get("format") != FORMAT or not checksums or fname not in checksums:
        return
    got = _sha256_file(d / fname)
    want = checksums[fname]
    if got != want:
        raise ArtifactCorruptError(
            f"{d / fname} is corrupt: sha256 {got[:16]}… does not match the "
            f"recorded {want[:16]}….  The artifact was truncated or "
            f"bit-flipped after save — re-run `launch.quantize --pack-out "
            f"{d}` to regenerate it, or pass verify=False "
            f"(launch.serve --no-verify) to serve it anyway at your own "
            f"risk.")


def _host_gather(x) -> np.ndarray:
    """Materialize a *full* (unsharded) array on host.

    This is the single host-gather of the module: the legacy write-back
    baseline (``RSQConfig.pack_writeback='host'``) routes every per-layer
    ``(q, scales, zeros)`` through it; the sharded write-back path never
    reaches it for any packed leaf."""
    return np.asarray(jax.device_get(x))


def _shard_items(x) -> list[tuple[list[list[int]], np.ndarray]]:
    """(index, local data) per distinct addressable shard of ``x``.

    Replicas beyond the first of each shard are skipped; a numpy array (the
    host-gather baseline) is a single full shard."""
    if isinstance(x, np.ndarray):
        return [([[0, d] for d in x.shape], x)]
    items, seen = [], set()
    for sh in x.addressable_shards:
        idx = tuple(s.indices(d)[:2] for s, d in zip(sh.index, x.shape))
        if idx in seen:
            continue
        seen.add(idx)
        items.append(([list(p) for p in idx], np.asarray(sh.data)))
    return items


def _save_field(arrays: dict, key: str, x) -> dict:
    """Append ``x`` to the write buffer one addressable shard at a time;
    returns the field's shard-index metadata for meta.json."""
    shards = _shard_items(x)
    for k, (idx, data) in enumerate(shards):
        arrays[f"{key}@{k}"] = data
    return {
        "shape": [int(s) for s in x.shape],
        "dtype": str(np.dtype(shards[0][1].dtype)),
        "shards": [idx for idx, _ in shards],
    }


def _assemble_field(z, key: str, fm: dict) -> np.ndarray:
    out = np.empty(tuple(fm["shape"]), np.dtype(fm["dtype"]))
    for k, idx in enumerate(fm["shards"]):
        sl = tuple(slice(lo, hi) for lo, hi in idx)
        data = z[f"{key}@{k}"]
        if data.dtype.kind == "V":  # .npz keeps bfloat16 as raw 2-byte void
            data = data.view(out.dtype)
        out[sl] = data
    return out


def save_packed_artifact(directory, artifact: dict, *,
                         params: Any = None, extra: dict | None = None,
                         ) -> Path:
    """Persist a pipeline artifact (``RSQPipeline.artifact``) to ``dir``.

    ``params``: the quantized param tree; its quantized leaves are replaced
    by empty markers and the remainder is stored as the fp residual so the
    loaders can reconstruct a complete model.  Residual leaves are written
    through the same per-addressable-shard path as the packed leaves — a
    vocab-sharded embedding or d_out-sharded router is never gathered into
    one controller buffer."""
    d = Path(directory)
    d.mkdir(parents=True, exist_ok=True)
    arrays: dict[str, np.ndarray] = {}
    meta_entries: dict[str, dict] = {}
    for name, entry in artifact["entries"].items():
        em = dict(artifact["meta"][name])
        em["fields"] = {
            field: _save_field(arrays, f"{name}/{field}", entry[field])
            for field in _FIELDS
        }
        meta_entries[name] = em

    meta = {"format": FORMAT, "spec": artifact["spec"],
            "entries": meta_entries, "extra": extra or {},
            "checksums": {}}
    if params is not None:
        residual = _strip_quantized(params, meta_entries)
        leaves, treedef = jax.tree_util.tree_flatten(residual)
        res_arrays: dict[str, np.ndarray] = {}
        meta["residual_leaves"] = [
            _save_field(res_arrays, f"leaf_{i}", leaf)
            for i, leaf in enumerate(leaves)
        ]
        meta["residual_treedef"] = pickle.dumps(treedef).hex()
        meta["checksums"]["residual.npz"] = _savez_atomic(
            d / "residual.npz", res_arrays)
    meta["checksums"]["packed.npz"] = _savez_atomic(d / "packed.npz", arrays)
    # meta.json last and atomically: its presence with checksums implies
    # the payload files it describes are complete
    tmp = d / "meta.tmp.json"
    tmp.write_text(json.dumps(meta))
    os.replace(tmp, d / "meta.json")
    return d


# --------------------------------------------------------------- tree paths


def _leaf_slot(params: Any, em: dict) -> tuple[Any, str, Optional[int]]:
    """(parent node, leaf key, stack index or None) for one entry's leaf."""
    kind = em["loc"][0]
    parts = em["path"].split("/")
    if kind == "prefix":
        node, g = params["prefix"][em["loc"][1]], None
    elif kind == "groups":
        node, g = params["groups"][f"b{em['loc'][2]}"], em["loc"][1]
    else:  # "enc"
        node, g = params["encoder"]["groups"]["b0"], em["loc"][1]
    for key in parts[:-1]:
        node = node[key]
    return node, parts[-1], g


def _strip_quantized(params: Any, meta_entries: dict) -> Any:
    """Copy of ``params`` with every packed leaf replaced by a marker (the
    whole stacked tensor, for group layers — each of its layer slices is
    reconstructed from the artifact)."""
    out = jax.tree.map(lambda x: x, params)
    if "prefix" in out:
        out["prefix"] = [jax.tree.map(lambda x: x, p) for p in out["prefix"]]
    marker = np.zeros((0,), np.int8)
    for em in meta_entries.values():
        node, leaf, _ = _leaf_slot(out, em)
        node[leaf] = marker
    return out


# -------------------------------------------------------------------- load


def _assemble_entry(z, name: str, em: dict) -> dict:
    return {field: _assemble_field(z, f"{name}/{field}", fm)
            for field, fm in em["fields"].items()}


def load_packed_artifact(directory, *, verify: bool = True
                         ) -> tuple[dict, dict]:
    """-> (entries, meta): per-entry assembled numpy ``codes/scale/zero``.

    Host memory holds only the packed representation; dequantization is the
    caller's (device-side) concern.  ``verify`` (default) checks
    ``packed.npz`` against the sha256 recorded in meta.json (v3 artifacts)
    and raises :class:`ArtifactCorruptError` on mismatch rather than
    serving silently corrupt codes."""
    d = Path(directory)
    meta = json.loads((d / "meta.json").read_text())
    assert meta["format"] in _READABLE, \
        f"unreadable artifact format {meta['format']!r}; " \
        f"re-run launch.quantize --pack-out (readable: {_READABLE})"
    if verify:
        _verify_file(d, meta, "packed.npz")
    with np.load(d / "packed.npz") as z:
        entries = {name: _assemble_entry(z, name, em)
                   for name, em in meta["entries"].items()}
    return entries, meta


def load_packed_entry(directory, name: str, *, verify: bool = False) -> dict:
    """Assemble a single entry's ``codes/scale/zero`` (npz members load
    lazily, so this reads just that weight's shards — handy for spot checks
    against a large artifact).  ``verify=True`` hashes the *whole*
    packed.npz first, which defeats the lazy read — default off here, on
    for the full-artifact loaders."""
    d = Path(directory)
    meta = json.loads((d / "meta.json").read_text())
    assert meta["format"] in _READABLE, \
        f"unreadable artifact format {meta['format']!r}; " \
        f"re-run launch.quantize --pack-out (readable: {_READABLE})"
    if verify:
        _verify_file(d, meta, "packed.npz")
    with np.load(d / "packed.npz") as z:
        return _assemble_entry(z, name, meta["entries"][name])


def dequantize_entry(entry: dict, em: dict, spec: dict) -> jax.Array:
    """One artifact entry -> fp weight, on device (packed bytes in, fp out)."""
    w = dequantize_packed(
        jnp.asarray(entry["codes"]), jnp.asarray(entry["scale"]),
        jnp.asarray(entry["zero"]), bits=int(spec["bits"]),
        d_in=int(em["d_in"]))
    return w.astype(em.get("dtype", "float32"))


def _load_residual(directory, meta: dict, *, verify: bool = True) -> Any:
    """Reassemble the fp residual tree from its per-shard members
    (v1 artifacts stored each leaf whole — load those as-is)."""
    d = Path(directory)
    if verify:
        _verify_file(d, meta, "residual.npz")
    with np.load(d / "residual.npz") as z:
        if "residual_leaves" in meta:
            leaves = [_assemble_field(z, f"leaf_{i}", fm)
                      for i, fm in enumerate(meta["residual_leaves"])]
        else:  # rsq-packed-v1
            leaves = [z[f"leaf_{i}"] for i in range(len(z.files))]
    treedef = pickle.loads(bytes.fromhex(meta["residual_treedef"]))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _stacked_slots(params: Any, meta: dict):
    """Group artifact entries by their target leaf: yields
    ``(node, leaf, em, per_layer)`` where ``per_layer`` maps the stacked
    layer index (or None for a plain leaf) to that layer's entry name."""
    def stack_key(em) -> tuple:
        o = em["loc"][2] if em["loc"][0] == "groups" else 0
        return (em["loc"][0], o, em["path"])

    stacked: dict[tuple, dict[int, str]] = {}
    stacked_em: dict[tuple, dict] = {}
    for name, em in meta["entries"].items():
        node, leaf, g = _leaf_slot(params, em)
        if g is None:
            yield node, leaf, em, {None: name}
        else:
            stacked.setdefault(stack_key(em), {})[g] = name
            stacked_em[stack_key(em)] = em
    for key, per_layer in stacked.items():
        n = max(per_layer) + 1
        assert sorted(per_layer) == list(range(n)), \
            f"artifact is missing layers for {key}: {sorted(per_layer)}"
        em = stacked_em[key]
        node, leaf, _ = _leaf_slot(params, em)
        yield node, leaf, em, {g: per_layer[g] for g in range(n)}


def load_packed_params(directory, *, verify: bool = True) -> tuple[Any, dict]:
    """-> (params, meta): a complete *dequantized* param tree for serving.

    The fp residual loads as saved; every quantized weight is rebuilt on
    device from its packed entry (group layers re-stack their per-layer
    entries along the stacked axis) — the unpacked weight never exists on
    host.  For packed-in-HBM serving (no fp weight anywhere) use
    :func:`load_packed_forward_params` instead.  ``verify`` checks both
    payload files against their recorded sha256 (v3) before loading."""
    d = Path(directory)
    entries, meta = load_packed_artifact(d, verify=verify)
    params = _load_residual(d, meta, verify=verify)
    for node, leaf, em, per_layer in _stacked_slots(params, meta):
        ws = [dequantize_entry(entries[per_layer[g]], em, meta["spec"])
              for g in sorted(per_layer, key=lambda g: -1 if g is None else g)]
        node[leaf] = ws[0] if None in per_layer else jnp.stack(ws)
    params = jax.tree.map(jnp.asarray, params)
    return params, meta


def load_packed_forward_params(directory, ctx: ParallelCtx = LOCAL, *,
                               verify: bool = True) -> tuple[Any, dict]:
    """-> (params, meta): serving params with the codes *kept packed in HBM*.

    Every quantized matrix lands in the tree as a ``PackedWeight`` pytree
    node (uint32 codes + per-group scale/zero; static quant geometry as
    aux data) that the model's ``linear`` dispatcher routes through the
    fused dequant-GEMM ``quant_matmul``.  No fp array of any quantized
    weight's full shape is ever created — not on host (shards reassemble
    in packed form) and not on device (the kernel dequantizes tile-wise
    in VMEM), with no exceptions: even MLA's absorbed decode contracts
    the per-head ``wkv_b`` views on packed codes (``mla_latent_weights``
    + the latent-layout ``quant_matmul_t`` — ``attention.mla_decode``).
    Resident weight HBM is therefore ~bits/16 of the bf16 model (bits/32
    of fp32) plus the small group params.

    Stacked layer groups re-stack per-layer *codes* along the leading
    axis, so the stacked ``PackedWeight`` rides the model's ``lax.scan``
    unchanged; expert entries keep their leading (E,) axis and dispatch
    through the vmapped kernel.  With a live mesh ``ctx``, codes / scale /
    zero are placed d_out-sharded on the model axis (the decode-serving
    layout: output-dim sharded weights, no per-token weight gathers) and
    the ``PackedWeight`` carries the (mesh, axis) placement in its aux, so
    ``quant_matmul`` can run the fused Pallas kernel per shard under
    ``shard_map`` instead of demoting sharded codes to the ref GEMM.

    ``verify`` (default) checks each payload file against the sha256
    recorded in meta.json before deserializing — a truncated or bit-
    flipped artifact fails with :class:`ArtifactCorruptError` instead of
    serving garbage codes."""
    d = Path(directory)
    entries, meta = load_packed_artifact(d, verify=verify)
    params = _load_residual(d, meta, verify=verify)
    spec = meta["spec"]

    def put(a: np.ndarray) -> tuple[jax.Array, bool]:
        a = jnp.asarray(a)
        if (ctx.enabled and ctx.tp
                and a.shape[-1] % ctx.axis_size("tp") == 0):
            from jax.sharding import NamedSharding, PartitionSpec as P
            sh = NamedSharding(ctx.mesh, P(*([None] * (a.ndim - 1)), ctx.tp))
            return jax.device_put(a, sh), ctx.axis_size("tp") > 1
        return a, False

    for node, leaf, em, per_layer in _stacked_slots(params, meta):
        order = sorted(per_layer, key=lambda g: -1 if g is None else g)
        fields = {}
        for f in _FIELDS:
            per = [entries[per_layer[g]][f] for g in order]
            fields[f] = per[0] if None in per_layer else np.stack(per)
        codes, sharded = put(fields["codes"])
        node[leaf] = PackedWeight(
            w_packed=codes, scale=put(fields["scale"])[0],
            zero=put(fields["zero"])[0], bits=int(spec["bits"]),
            group_size=int(em["group_size"]), d_in=int(em["d_in"]),
            # partitioned codes must never reach GSPMD as an opaque Pallas
            # call (it would all-gather them); the (mesh, axis) aux lets
            # quant_matmul shard_map the kernel over the model axis, with
            # the partitionable ref GEMM as its fallback
            mesh_sharded=sharded,
            mesh=ctx.mesh if sharded else None,
            mesh_axis=ctx.tp if sharded else None)
    params = jax.tree.map(jnp.asarray, params)
    return params, meta
