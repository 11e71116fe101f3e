"""A cell whose weights come from a ``weights_key`` holds its limits against
readings taken on THAT draw.  ``limits/<cell>.read-on.json`` records the key
the readings were taken on, as data beside ``limits/<cell>.json``; a change
of the configuration's key that forgets to read the limits again fails here.
"""

import json
import os

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
KEYED = [w["name"] for w in BENCH["workloads"]
         if "weights_key" in spec.resolve(w["name"]).sizes]


@pytest.mark.parametrize("name", KEYED)
def test_the_readings_are_on_the_weights_the_cell_runs(name):
    path = os.path.join(spec.CHECKOUT, "chipbench", "limits",
                        name + ".read-on.json")
    with open(path) as handle:
        read_on = json.load(handle)
    assert read_on["weights_key"] == spec.resolve(name).sizes["weights_key"]

