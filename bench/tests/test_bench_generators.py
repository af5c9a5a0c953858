"""The data generator: a seed gives the same points in any process
(Python's salted ``hash()`` plays no part), blocks do not depend on how a
batch straddles them, and one fixed standardisation holds for every
block."""

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

CONFIGS = ["blobs-d10", "covertype-d54"]
SEED = 2**31 + 77

_DIGEST = """
import hashlib, json, sys
sys.path.insert(0, {bench!r})
import harness
conf = json.loads(open({conf!r}).read())
gen = harness._load_module(harness.BENCH / "generators"
                           / (conf["generator"] + ".py")).Generator(
    conf["data"], {seed})
h = hashlib.sha256()
for b in (0, 7, 1234567):
    h.update(gen.block(b).tobytes())
print(h.hexdigest())
"""


def _generator(config, seed):
    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    mod = harness._load_module(BENCH / "generators"
                               / f"{conf['generator']}.py")
    return mod.Generator(conf["data"], seed), conf


@pytest.mark.parametrize("config", CONFIGS)
def test_one_seed_gives_the_same_points_in_two_processes(config):
    code = _DIGEST.format(bench=str(BENCH), seed=SEED,
                          conf=str(BENCH / "configs" / f"{config}.json"))
    digests = []
    for salt in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=salt)
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120,
                           check=True)
        digests.append(p.stdout.strip())
    gen, _ = _generator(config, SEED)
    h = hashlib.sha256()
    for b in (0, 7, 1234567):
        h.update(gen.block(b).tobytes())
    assert digests[0] == digests[1] == h.hexdigest()


@pytest.mark.parametrize("config", CONFIGS)
def test_seeds_differ_and_blocks_are_standardised(config):
    """Points differ from seed to seed, except in the configuration's fixed
    initial set, and from block to block: the stream never wraps."""
    gen, conf = _generator(config, SEED)
    other, _ = _generator(config, SEED + 1)
    X = np.concatenate([gen.block(b) for b in range(40)])
    assert X.shape == (40 * conf["data"]["block"], conf["d"])
    first = conf["data"].get("initial_blocks", 0)
    assert np.array_equal(gen.block(0), other.block(0)) == (first > 0)
    assert not np.array_equal(gen.block(first), other.block(first))
    far = conf["live_window"] // conf["data"]["block"]
    assert not np.array_equal(gen.block(3), gen.block(3 + far))
    assert np.abs(X.mean(0)).max() < 0.1
    assert np.abs(X.std(0) - 1).max() < 0.1


def test_stream_positions_do_not_depend_on_the_batch():
    gen, _ = _generator("covertype-d54", SEED)
    s = harness.Stream(gen)
    whole = s.range(700, 3300)
    parts = np.concatenate([s.take(p, 500) for p in range(700, 3300, 500)]
                           + [s.take(3200, 100)])
    assert np.array_equal(whole, parts[:2600])
    assert np.array_equal(s.take(999, 2), whole[299:301])
