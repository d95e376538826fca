"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip behaviour is tested without TPU hardware via XLA's host
platform device count — the JAX idiom for "multi-node without a
cluster". Must run before jax is imported anywhere.
"""

import os

# Hard-set (not setdefault): on a machine with a chip JAX would pick it;
# tests always run on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# float64 oracles, here and in every child process a test spawns: the
# product runs float32 (chip_smoke.py checks that configuration on the
# chip); tier-1 compares against numpy/sklearn in double precision.
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest


@pytest.fixture()
def store():
    from learningorchestra_tpu.core.store import InMemoryStore

    return InMemoryStore()


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


TITANIC_LIKE_CSV = """PassengerId,Survived,Pclass,Name,Sex,Age,SibSp,Parch,Fare,Embarked
1,0,3,"Braund, Mr. Owen",male,22,1,0,7.25,S
2,1,1,"Cumings, Mrs. John",female,38,1,0,71.2833,C
3,1,3,"Heikkinen, Miss. Laina",female,26,0,0,7.925,S
4,1,1,"Futrelle, Mrs. Jacques",female,35,1,0,53.1,S
5,0,3,"Allen, Mr. William",male,35,0,0,8.05,S
6,0,3,"Moran, Mr. James",male,,0,0,8.4583,Q
7,0,1,"McCarthy, Mr. Timothy",male,54,0,0,51.8625,S
8,0,3,"Palsson, Master. Gosta",male,2,3,1,21.075,S
"""


@pytest.fixture()
def titanic_csv(tmp_path):
    path = tmp_path / "titanic.csv"
    path.write_text(TITANIC_LIKE_CSV)
    return str(path)
