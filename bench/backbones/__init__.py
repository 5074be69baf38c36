"""Backbone families, one module each, found by the name a configuration
file gives under ``backbone`` (``bench/backbones/<backbone>.py``).

A module holds all that the drivers need of one architecture, and the
drivers call nothing else of it:

``program_config(config)``   the program's ``TransformerConfig``
``sizes(config)``            the model sizes by short names
``init_params(config, seed)`` and ``init_state(config, seed)``
                             seeded f32 weights (and AdamW state at
                             zero), on the device in one jitted call
``train_readings(config, seed, batches, quant=False)``
``encode_readings(config, seed, tokens, mask, indices, *, block,
quant=False)``
``change_norms(params, config, seed)``
                             the plain reference's readings
                             (``bench.compare`` defines them)
``step_work(config, batch)`` and ``encode_work(config, batches)``
                             the work of one training step and of the
                             given encoded batches: the head's ``Work``
                             (``head_fwd``, and ``head_dh``/``head_de``
                             in training) and ``model_flops``; further
                             keys are counters the readers may take
``SMALL``                    the configuration keys set to a size a CPU
                             test run holds (``bench/tests/_small.py``)

A new architecture adds a module and a configuration that names it; the
drivers and the shared pieces (``bench/weights.py``,
``bench/reference.py``, ``bench/work.py``) stay as they are.
"""

import importlib

INTERFACE = ("program_config", "sizes", "init_params", "init_state",
             "train_readings", "encode_readings", "change_norms",
             "step_work", "encode_work", "SMALL")


def load(config):
    """The backbone module a configuration names."""
    return importlib.import_module(f"bench.backbones.{config['backbone']}")
