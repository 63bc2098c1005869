import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def shrink(config: dict, mix: dict) -> tuple:
    """The committed configuration and mix at a size a CPU test holds:
    every knob the generator reads, with small numbers."""
    config = dict(config, validators=48, committee_size=8,
                  committees_per_slot=2, unit_message_pool=3)
    config["unit"] = dict(config["unit"],
                          per_committee=min(config["unit"]["per_committee"], 8))
    mix = dict(mix, shape_seed=mix["shape_seed"], warm_sizes=[64],
               reference_sample=3)
    if mix["loop"] == "open":
        mix.update(rate_sets_per_s=12, submission_units={"log_uniform": [1, 6]})
    else:
        mix.update(capacity_sets_per_s=40, submission_units=2, tenants=2,
                   in_flight_per_tenant=1, poison_span=0.3)
    return config, mix


@pytest.fixture
def tiny():
    from benchmark import traffic as T

    def make(config_name, mix_name):
        return shrink(T.load("configs", config_name), T.load("traffic", mix_name))

    return make
