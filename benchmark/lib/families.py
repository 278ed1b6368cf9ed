"""The table of model families: which counting module the readers that are
ONE metric for every family take (`trainer.mfu.lm`, `trainer.attn_roofline.lm`).

A row is a file: the family ``<name>`` is ``benchmark/lib/<name>shapes.py``,
and a driver says which family its cell is by the ONE key ``family`` it
writes into ``ctx.shapes`` (``benchmark/drivers/lm*.py``), so the choice is
exclusive whatever other keys two families share (two drivers may name one
family: ``lm_mla.py`` and ``lm_glm.py`` are both ``mla``).

A row's columns are the module's names:

    COUNTERS                  the trainer's counters the step's operations
                              are counted from, in ``step_flops``' order
    step_flops(*counts, s)    operations of the window's steps
    ATTENTION_SCOPES          the attention kernels' scopes; absent where
                              the family has no kernel of its own to set a
                              roofline against
    attention_step_flops(s)   ONE step's attention proper, every layer

A later PR's new family is a new ``<name>shapes.py`` with these names, a
driver that writes ``family="<name>"``, and its cell appended to the two
readers' ``workloads`` in ``BENCHMARK.json``: no file that is here is
edited, and no new entry is listed for what is one question.
"""

import importlib
import re

_NAME = re.compile(r"[a-z][a-z0-9_]*\Z")


def counting(shapes):
    """The counting module of the family ``shapes`` names; None for shapes
    of no family (a rows or SGNS cell, a driver from before the key)."""
    family = (shapes or {}).get("family")
    if not isinstance(family, str) or not _NAME.match(family):
        return None
    try:
        module = importlib.import_module(f"benchmark.lib.{family}shapes")
    except ModuleNotFoundError:
        return None
    return module if hasattr(module, "COUNTERS") else None
