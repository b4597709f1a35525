"""The default architecture (arch/granite_swat.py) gives what the benchmark
gave before architectures were files: the cost of the 8B configuration,
the weights and the reference's gaps, read from the earlier code on the
CPU and written here as literals."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import arch
import cost
import weights as W
from tiny import dense, moe

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "granite-8b-swat.json").read_text())
# sixteen slots at decode-long positions, past the 2177-row ring
POSITIONS = [2304, 2560, 2817, 3001, 3333, 3600, 3999, 4096, 4321, 4700,
             5000, 5312, 5555, 5800, 6000, 6143]


@pytest.mark.parametrize("length,flops", [
    (32, 251813953536.0), (384, 3037269590016.0), (2048, 16699537489920.0),
    (4096, 34092324225024.0)])
def test_prefill_flops_unchanged(length, flops):
    assert cost.prefill_flops(CFG, length) == flops


def test_decode_flops_and_bytes_unchanged():
    assert cost.decode_flops(CFG, POSITIONS) == 142342619136.0
    assert cost.decode_step_bytes(CFG, POSITIONS) == 10823090176.0


def _digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("make,seed,digest", [
    (dense, 7, "378dd1aa706b0fac89318171a31cc99d03b8d01196d475658859a642049e1d9a"),
    (dense, 2**31 + 5, "715faaebe1403ee4e7b06e6be6edefd590b4bd3eebdd526364698fb65e5127a1"),
    (moe, 7, "292b34264dab138eca286e57e17a4879e8bc47588553014bd90e7481a360f741"),
    (moe, 2**31 + 5, "6af1f75487b631aeab6f19c351859233a2a61feb5f47b3b8c7aba3b8967f1b87"),
], ids=["dense-7", "dense-2^31+5", "moe-7", "moe-2^31+5"])
def test_weights_bitwise_unchanged(make, seed, digest):
    assert _digest(W.make(make(), seed)) == digest


GAPS = {
    "dense": [3.268263339996338, 2.9100847244262695, 2.9059815406799316,
              1.3257182836532593, 2.9119510650634766, 2.587522029876709,
              2.3001246452331543, 4.189589500427246, 3.357919216156006,
              4.117640972137451, 1.679927110671997, 3.590160369873047,
              3.3124070167541504, 2.650294780731201, 4.445263385772705,
              2.6994845867156982, 2.291234016418457, 3.1749610900878906,
              1.6913950443267822, 1.914992094039917],
    "moe": [2.897272825241089, 3.1964011192321777, 3.0094568729400635,
            3.2965660095214844, 3.332953453063965, 3.1284704208374023,
            2.535710573196411, 2.030285596847534, 4.380288124084473,
            1.0085101127624512, 3.0126729011535645, 2.3380534648895264,
            2.780585527420044, 2.928454637527466, 1.0956156253814697,
            4.035845756530762, 3.7064027786254883, 4.7881903648376465,
            2.8312642574310303, 1.8071366548538208]}
ARGMAX = {"dense": [248, 126, 227, 126, 145, 233, 229, 194, 233, 182, 176, 176,
                    176, 176, 27, 166, 166, 166, 222, 26],
          "moe": [113, 67, 92, 86, 92, 18, 49, 114, 253, 74, 159, 242, 242, 111,
                  18, 18, 45, 119, 45, 11]}


def test_reference_gaps_unchanged():
    """40 random tokens and 20 random served tokens per configuration, the
    dense one's drawn first from one generator."""
    rng = np.random.default_rng(3)
    for make in (dense, moe):
        cfg = make()
        seq = rng.integers(0, cfg["vocab_size"], 40).astype(np.int32)
        tok = rng.integers(0, cfg["vocab_size"], 20).astype(np.int32)
        gap, arg, _, decisive = arch.load(cfg).score(
            W.make(cfg, 7), cfg, seq, np.arange(20, 40, dtype=np.int32), tok)
        assert gap.tolist() == GAPS[make.__name__]
        assert arg.tolist() == ARGMAX[make.__name__]
        assert decisive.all()
