"""Post-training int8 quantization (PTQ) of a verified checkpoint.

Per-channel symmetric weight quantization over the quantizable layer
types (fullc / conv / seqfc — everything whose ``wmat`` keeps its
output channels on the last axis), with activation scales calibrated
from a small batch stream (abs-max, optionally percentile-clipped).

The quantized layer's params carry everything the int8 execution path
(ops/quant.py) needs, INSIDE the ordinary params tree:

    {"wmat":       int8, same shape as the source weight,
     "wmat_scale": f32 per-out-channel vector,
     "act_scale":  f32 scalar (calibrated activation clip),
     "bias":       untouched f32}

Because scales are plain leaves under ``params/<layer>/...`` they flow
through every existing surface unchanged: checkpoint digests cover
them, ``trainer._place`` replicates them (missing pspec keys fall back
to replicated), the engine's compiled closures take them as jit
arguments (hot reload stays zero-recompile), and layers detect the
quantized form by the presence of ``wmat_scale``.

The derived checkpoint round carries ``__quant_meta__`` in its meta
JSON (checkpoint.quant_meta): source round + blob_digest, calibration
config, and per-leaf drift metrics — the provenance chain the deploy
reject-list and tools/ckpt_health.py key on.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from .. import checkpoint as ckpt
from ..config import QuantConfig
from ..telemetry.ledger import LEDGER

#: layer types eligible for weight quantization: their ``wmat`` stores
#: output channels on the LAST axis (fullc (in,out), conv HWIO, seqfc
#: (e,k)), which is what per-channel symmetric scaling assumes.
#: embed/posembed/mha/norm/moe stay fp32 — their weights either feed
#: gathers (no matmul to quantize) or carry params int8 would distort.
QUANT_LAYER_TYPES = ("fullc", "conv", "seqfc")

_TINY = 1e-12


def quantizable_layers(net) -> "Dict[str, str]":
    """Map quantizable layer name -> its input node name (the node whose
    captured activations calibrate ``act_scale``). Shared (weight-tied)
    layers reuse the primary's params entry, so each name appears once."""
    g = net.graph
    out: Dict[str, str] = {}
    for spec in g.layers:
        if spec.type in QUANT_LAYER_TYPES and not spec.is_shared \
                and spec.name not in out:
            out[spec.name] = g.node_names[spec.nindex_in[0]]
    return out


def _rms(a: np.ndarray) -> float:
    return float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))


def quantize_weight(w: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-out-channel symmetric int8: scale[c] = absmax(|w[..., c]|)/127
    (all-zero channels get scale 1 so dequant stays exact)."""
    w = np.asarray(w, np.float32)
    absmax = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)))
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return q, scale


def weight_drift(w: np.ndarray, q: np.ndarray,
                 scale: np.ndarray) -> Dict[str, float]:
    """Round-trip drift of one quantized leaf: relative RMS error of
    dequant(q) vs the source weight, and the saturation fraction
    (|q| == 127 — a high fraction means the per-channel range clipped
    real mass, the classic sign of an outlier channel)."""
    w = np.asarray(w, np.float32)
    deq = q.astype(np.float32) * scale
    return {
        "rel_err": _rms(deq - w) / max(_rms(w), _TINY),
        "sat_frac": float(np.mean(np.abs(q.astype(np.int32)) == 127)),
    }


def calibrate_act_scales(net, params, state, batches: Iterable[Any],
                         percentile: float = 100.0) -> Dict[str, float]:
    """Run the source (fp) model over the calibration stream with node
    capture on and record, per quantizable layer, the max over batches
    of the |input| abs-max (percentile < 100 clips each batch's tail
    first — rare outliers trade for int8 resolution). Batches are NHWC
    arrays as the engine feeds them."""
    targets = quantizable_layers(net)
    scales: Dict[str, float] = {}
    n_batches = 0
    for batch in batches:
        res = net.apply(params, state, batch, train=False,
                        capture_nodes=True)
        n_batches += 1
        for lname, node in targets.items():
            v = res.nodes.get(node)
            if v is None:
                continue
            v = np.abs(np.asarray(v, np.float32))
            s = float(np.max(v)) if percentile >= 100.0 \
                else float(np.percentile(v, percentile))
            scales[lname] = max(scales.get(lname, 0.0), s)
    if not n_batches:
        raise ValueError("quantize: calibration stream yielded no batches")
    # a layer whose input never fired (or is all-zero) calibrates to 1.0
    # rather than 0 (a zero act_scale would divide out the whole input)
    return {ln: (scales.get(ln) or 1.0) for ln in targets}


def quantize_params(params: Dict[str, Any],
                    act_scales: Dict[str, float]
                    ) -> Tuple[Dict[str, Any], Dict[str, Dict[str, float]]]:
    """Produce the quantized params tree (source tree untouched) plus
    per-layer drift metrics. Only layers named in ``act_scales`` with a
    ``wmat`` leaf quantize; everything else passes through by
    reference."""
    out: Dict[str, Any] = {}
    drift: Dict[str, Dict[str, float]] = {}
    for lname, lp in params.items():
        if lname in act_scales and isinstance(lp, dict) and "wmat" in lp:
            w = np.asarray(lp["wmat"])
            q, scale = quantize_weight(w)
            qp = dict(lp)
            qp["wmat"] = q
            qp["wmat_scale"] = scale
            qp["act_scale"] = np.float32(act_scales[lname])
            out[lname] = qp
            drift[lname] = weight_drift(w, q, scale)
        else:
            out[lname] = lp
    return out, drift


def dequantize_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Fold the scales back in: int8 wmat -> f32 wmat, scale leaves
    dropped. Structure-compatible with the source checkpoint (used by
    fp engines negotiating a quantized blob, and by the deploy gate's
    quantized-vs-incumbent comparison)."""
    out: Dict[str, Any] = {}
    for lname, lp in params.items():
        if isinstance(lp, dict) and "wmat_scale" in lp:
            qp = dict(lp)
            scale = np.asarray(qp.pop("wmat_scale"), np.float32)
            qp.pop("act_scale", None)
            qp["wmat"] = np.asarray(qp["wmat"], np.float32) * scale
            out[lname] = qp
        else:
            out[lname] = lp
    return out


def is_quantized_params(params: Dict[str, Any]) -> bool:
    """Whether any layer in the tree carries the int8 form."""
    return any(isinstance(lp, dict) and "wmat_scale" in lp
               for lp in params.values())


def dequantize_blob(blob: Dict[str, Any]) -> Dict[str, Any]:
    """Blob-level :func:`dequantize_params` (meta/state pass through;
    the meta keeps ``__quant_meta__`` so provenance survives)."""
    out = dict(blob)
    out["params"] = dequantize_params(blob["params"])
    return out


def drift_verdict(qm: Dict[str, Any], max_rel_err: float,
                  max_sat_frac: float) -> Dict[str, Any]:
    """Quantized-vs-source verdict over the drift metrics stored in a
    ``__quant_meta__`` block: SAFE when every quantized leaf's relative
    RMS error and saturation fraction clear the thresholds. Shared by
    tools/ckpt_health.py (human report) and deploy's offline gate (a
    drift-unsafe quantized round never reaches a canary)."""
    rows: List[Dict[str, Any]] = []
    worst_err = worst_sat = 0.0
    offenders = []
    for lname in sorted(qm.get("drift", {})):
        d = qm["drift"][lname]
        ok = (d["rel_err"] <= max_rel_err
              and d["sat_frac"] <= max_sat_frac)
        if not ok:
            offenders.append(lname)
        worst_err = max(worst_err, d["rel_err"])
        worst_sat = max(worst_sat, d["sat_frac"])
        rows.append({"layer": lname, "rel_err": d["rel_err"],
                     "sat_frac": d["sat_frac"], "ok": ok})
    ok = not offenders and bool(rows)
    verdict = "SAFE" if ok else "UNSAFE"
    line = (f"quant drift {verdict}: {len(rows)} quantized layers, "
            f"worst rel_err {worst_err:.4f} (max {max_rel_err}), "
            f"worst sat_frac {worst_sat:.4f} (max {max_sat_frac})"
            + (f"; offenders: {', '.join(offenders)}" if offenders
               else ""))
    return {"ok": ok, "verdict": verdict, "line": line, "layers": rows,
            "worst_rel_err": worst_err, "worst_sat_frac": worst_sat,
            "source_round": qm.get("source_round"),
            "source_digest": qm.get("source_digest")}


def quantize_blob(net, blob: Dict[str, Any], batches: Iterable[Any],
                  qc: QuantConfig) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Full PTQ pass over a loaded inference blob: calibrate activation
    scales on the fp model, quantize the weights, and assemble the
    ``__quant_meta__`` provenance block. Returns ``(qblob, quant_meta)``
    — the caller decides the output round (write_quantized_round).
    Emits the ``quant_calibrate`` ledger event."""
    t0 = time.perf_counter()
    src_digest = ckpt.blob_digest(blob["meta"])
    act_scales = calibrate_act_scales(
        net, blob["params"], blob["state"], batches,
        percentile=qc.calib_percentile)
    qparams, drift = quantize_params(blob["params"], act_scales)
    if not drift:
        raise ValueError(
            "quantize: model has no quantizable layers "
            f"(looked for {', '.join(QUANT_LAYER_TYPES)})")
    qm = {
        "quant_dtype": "int8",
        "source_round": int(blob["meta"]["round"]),
        "source_digest": src_digest,
        "calib": {"batches": int(qc.calib_batches),
                  "percentile": float(qc.calib_percentile)},
        "act_scales": {k: float(v) for k, v in act_scales.items()},
        "quantized_layers": sorted(drift),
        "drift": {k: {"rel_err": float(v["rel_err"]),
                      "sat_frac": float(v["sat_frac"])}
                  for k, v in drift.items()},
    }
    qblob = dict(blob)
    qblob["params"] = qparams
    LEDGER.event("quant_calibrate",
                 source_round=qm["source_round"],
                 source_digest=src_digest,
                 layers=len(drift),
                 percentile=float(qc.calib_percentile),
                 seconds=round(time.perf_counter() - t0, 4))
    return qblob, qm


def write_quantized_round(path: str, structure_sig: tuple,
                          qblob: Dict[str, Any],
                          qm: Dict[str, Any]) -> None:
    """Persist the derived round: same structure signature as the
    source (quantization changes leaves, not the DAG), source round's
    epoch/step carried through, ``__quant_meta__`` riding the meta
    JSON. The archive gets its own digests, so ``blob_digest`` of the
    quantized round is a distinct content identity."""
    meta = qblob["meta"]
    ckpt.save_model(
        path, structure_sig=structure_sig,
        round_counter=int(meta["round"]),
        epoch_counter=int(meta["epoch"]),
        params=qblob["params"], net_state=qblob["state"],
        opt_state=None,
        step_count=int(meta.get("step_count", 0)),
        lr_scale=float(meta.get("lr_scale", 1.0)),
        extra_meta={"__quant_meta__": qm})
