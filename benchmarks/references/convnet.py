"""Reference module ``convnet``: what a configuration of the benchmark's
convnets brings to ``benchmarks/run.py`` (its header has the contract).
It is the reference of every configuration file that names none.

A thin module over ``benchmarks/reference.py`` (the plain float32
``lax`` forward of the twelve layer kinds) and ``benchmarks/flops.py``
(conv and fullc operations from shapes). ``check`` is the reference's
part of ``correct``, one kind per loss shape:

* ``train_loss`` — the program's first-step loss against the reference's
  on a copy of the initial weights and the loop's first batch (the
  flagship: batch norm in training mode over the whole batch);
* ``eval_logits`` — ``Trainer.predict_raw`` on the first batch against
  the reference's eval-mode forward (AlexNet: the dropout mask is the
  program's own, so the train-mode loss has no reference).

The two functions and their tolerances stand here as PR 23 wrote them
in ``run.py``, arithmetic and limits unchanged (moved in PR 27).
"""

from __future__ import annotations

import math

from benchmarks import flops, reference

#: |program loss - reference loss| at the first step. The program
#: computes in bfloat16 (8 bits of mantissa) against the reference's
#: float32: two summation orders of the same bf16 step differ by 2.8e-4
#: on the flagship (PERF.md, PR 21) and program and reference by 6.3e-4
#: (my chip run, PR 23); the bound is PR 21's 5e-3, a fifth of one
#: bfloat16 epsilon (2**-8) of a loss of 6.9. float32 cells (the CPU
#: rehearsal) are held to 1e-3.
LOSS_TOL = {"bfloat16": 5e-3, "float32": 1e-3}
#: eval-mode logits (centred log-softmax), worst element over the
#: largest reference element: each bf16 rounding is 2**-8 = 0.4 % and
#: AlexNet stacks eight weighted layers.
LOGIT_TOL = {"bfloat16": 5e-2, "float32": 1e-3}


def check_train_loss(ref, layers, defaults, params0, batch0, loss0, dtype):
    import jax
    import numpy as np
    data = ref.normalise(batch0.data, batch0.norm)
    fn = jax.jit(ref.make_loss_fn(layers, defaults))
    want = float(fn(params0, data, np.asarray(_host_label(batch0))))
    tol = LOSS_TOL[dtype]
    ok = math.isfinite(want) and abs(loss0 - want) <= tol
    return ok, {"check": "train_loss", "program": loss0, "reference": want,
                "abs_diff": abs(loss0 - want), "tolerance": tol}


def check_eval_logits(ref, layers, defaults, tr, batch0, dtype):
    import jax
    import numpy as np
    got = ref.centered_log(tr.predict_raw(batch0))
    data = ref.normalise(batch0.data, batch0.norm)
    fn = jax.jit(ref.make_eval_fn(layers, defaults))
    want = ref.centered_log(np.asarray(fn(tr.params, tr.net_state, data)))
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    tol = LOGIT_TOL[dtype]
    return (math.isfinite(err) and err <= tol), {
        "check": "eval_logits", "rel_err": err, "tolerance": tol,
        "max_abs_logit": float(np.max(np.abs(want)))}


def _host_label(batch):
    return batch.host_label if batch.host_label is not None else batch.label


# -- the contract ---------------------------------------------------------


def needs_initial_params(kind: str) -> bool:
    """``train_loss`` compares the FIRST step, whose arguments the step
    donates: the harness keeps a copy of the initial weights for it."""
    return kind == "train_loss"


def check(kind: str, view: dict):
    if kind == "train_loss":
        return check_train_loss(
            reference, view["layers"], view["defaults"], view["params0"],
            view["batch0"], view["warm_losses"][0], view["dtype"])
    if kind == "eval_logits":
        return check_eval_logits(
            reference, view["layers"], view["defaults"], view["trainer"],
            view["batch0"], view["dtype"])
    raise ValueError(f"references/convnet.py has no check {kind!r}")


def train_step_flops(view: dict) -> float:
    """Conv and fullc operations of one step at ``view["rows"]`` rows:
    the reference's forward walked over shapes (nothing runs) into
    ``flops.train_step_flops`` — 11.960 GFLOP an image for
    ``inception_bn``, 4.1356 for ``alexnet``."""
    import jax
    import numpy as np
    tr = view["trainer"]
    records = []
    jax.eval_shape(lambda p, d: reference.forward(
        view["layers"], view["defaults"], p, {}, d, True, record=records),
        tr.params, jax.ShapeDtypeStruct(
            (view["rows"],) + tuple(np.shape(view["batch0"].data)[1:]),
            np.float32))
    return flops.train_step_flops(records)
