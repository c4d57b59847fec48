"""Plain float32 references, written from the published descriptions.

They import nothing of the program under test and take nothing it made:
weights, data and every step are computed here from the seed.  A
configuration names its architecture's module under ``reference``; each
such module has ``init``, ``loss``, ``to_program``/``from_program``,
``expect`` and the counts ``param_count``, ``matmul_params`` and
``attention_flops_per_token``.
"""

import importlib


def model(config: dict):
    """The reference module of a configuration's architecture."""
    return importlib.import_module(f"chipbench.reference.{config['reference']}")
