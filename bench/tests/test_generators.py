"""The ``hurr`` generator: deterministic per seed, and every seed the same
work."""

import json

import numpy as np
import pytest

from bench import manifest

surrogate = manifest.module("generators", "hurr")

TINY = dict(field_shape=[3, 16, 100], fields_per_step=3, chunk_symbols=512)


def cfg(name):
    c = json.loads((manifest.BENCH / "configs" / f"{name}.json").read_text())
    return dict(c, **TINY)


def host(fields):
    return [np.asarray(f) for f in fields]


@pytest.mark.parametrize("name", ["hurr-quant-u16", "hurr-field-f32"])
def test_same_seed_same_inputs_other_seed_other_bytes(name):
    c = cfg(name)
    big = 2**33 + 17  # seeds wider than 32 bits reach the generator whole
    a, b = host(surrogate.time_step(c, big)), host(surrogate.time_step(c, big))
    other = host(surrogate.time_step(c, big + 2**32))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not any(np.array_equal(x, y) for x, y in zip(a, other))
    assert a[0].shape == tuple(TINY["field_shape"])
    assert a[0].dtype == np.dtype(c["dtype"])
    assert not np.array_equal(a[0], a[1])  # fields differ within a step


@pytest.mark.parametrize("name", ["hurr-quant-u16", "hurr-field-f32"])
def test_seeds_keep_which_symbols_are_equal_within_each_chunk(name):
    c = cfg(name)
    ch = c["chunk_symbols"]
    a = host(surrogate.time_step(c, 1))[0].reshape(-1)
    b = host(surrogate.time_step(c, 2))[0].reshape(-1)
    bits = np.uint16 if c["dtype"] == "uint16" else np.uint32
    a, b = a.view(bits), b.view(bits)
    full = a.size // ch * ch
    xa, xb = a[:full].reshape(-1, ch), b[:full].reshape(-1, ch)
    # one constant per chunk, in the low half of the symbol only
    diff = xa ^ xb
    assert (diff == diff[:, :1]).all()
    assert (diff >> (8 * bits().itemsize // 2) == 0).all()
    assert np.array_equal(a[full:], b[full:])  # the partial chunk is kept


def test_quant_codes_are_centred_lorenzo_codes():
    codes = host(surrogate.time_step(cfg("hurr-quant-u16"), 5))[0]
    near = np.abs(codes.astype(np.int64) - surrogate.CENTER) < 512
    assert near.mean() > 0.99


def test_seed_out_of_range_is_refused():
    with pytest.raises(ValueError):
        surrogate.split_seed(-1)
