"""Plain float32 references of the benchmark's training jobs.

Written from the published descriptions and the configuration files in
``bench/configs``, in straightforward ``jax.numpy``; they import nothing
of the program (``repro``) and take nothing it made.  Departures of the
program from the published models are listed in each configuration's
``assumed``; the reference follows the program there, so that what is
compared is the program's arithmetic, not its choice of model.

A configuration file names its reference: ``"reference": "<module>"``
selects ``bench/reference/<module>.py`` (``module_for``), and a file
without the key takes ``lm``.  So a model that ``lm`` does not compute
joins the benchmark with a module of its own and no edit to the harness.
Each reference module exports, with ``a`` the file's ``arch`` dict:

``leaf_specs(a)``
    parameter path -> (shape, init scale), the paths the program's own
    parameter tree gives;
``init(a, seed)``
    the initial weights for the seed, made on the device;
``loss(a, p, tokens, *, dt, prec)``
    the training loss of ``tokens`` (B, S), with ``dt``/``prec`` one of
    ``lm``'s arithmetics: float32 at ``lm.HIGHEST`` (the reference), or
    bfloat16 at ``lm.DEFAULT`` or ``lm.FP8`` (the controls);
``flops_per_token(a, seq_len)``
    the operations a training step requires per token, as ``step_mfu``
    reads them;
``file_only(arch)``
    the keys a file's ``arch`` may state that the program's
    ``ArchConfig`` has no field of that name for: parameters of this
    reference, each at the value the program runs.  The harness compares
    a stated one with it, and refuses a key that is neither a field nor
    one of these;
``unmodelled(arch, a)``
    the features of the program's ``ArchConfig`` that this reference
    does not compute, each by a short name; ``a`` because some are
    stated by the file (the attention's scale).  The harness refuses a
    configuration for which this is not empty;
``tiny(cfg)``
    the configuration file's dict at a size the CPU tests hold, its
    structure kept.

The precisions (``HIGHEST``, ``DEFAULT``, ``FP8``) stay in ``lm``, and a
module may build on ``lm``'s pieces (``matmul``, ``rms_norm``, ``rope``).
"""
from __future__ import annotations

import importlib
from types import ModuleType
from typing import Dict

DEFAULT_MODULE = "lm"


def module_for(cfg: Dict) -> ModuleType:
    """The reference module the configuration file names."""
    name = cfg.get("reference", DEFAULT_MODULE)
    if not name.isidentifier():
        raise ValueError(f"{cfg['name']}: reference {name!r} is not a "
                         "module name under bench/reference")
    return importlib.import_module(f"{__name__}.{name}")
