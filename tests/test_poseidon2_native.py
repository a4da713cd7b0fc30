"""The host Poseidon2 in native/poseidon2.c against its Python bodies.

`ops/poseidon2.permute_ref` and `models/poseidon2_air.generate_trace`
take the native engine where it loaded; the Python bodies stay as the
fallback and as the oracle here.  Everything a proof is made of (each
AIR's trace, its public inputs, the binding sponge) has to be the same
bytes on both paths.  Host work only: nothing here compiles a JAX
program."""

import random
import sys
import threading

import numpy as np
import pytest

from ethrex_tpu.models import poseidon2_air as pair
from ethrex_tpu.ops import babybear as bb
from ethrex_tpu.ops import poseidon2 as p2
from ethrex_tpu.prover import tpu_backend
from ethrex_tpu.utils import tracing


def _states(seed: int, n: int = 100) -> list:
    rng = random.Random(seed)
    return [[rng.randrange(bb.P) for _ in range(16)] for _ in range(n)]


EDGES = [[0] * 16, [bb.P - 1] * 16, list(range(16))]


def test_the_native_engine_builds_and_loads_here():
    assert p2.available() is True


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_native_permutation_equals_the_python_body(seed):
    for state in _states(seed) + EDGES:
        assert p2.permute_ref(state) == p2._permute_py(state)


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_native_trace_rows_equal_the_python_body(seed):
    for state in _states(seed) + EDGES:
        got = pair.generate_trace(state)
        want = pair._generate_trace_py(state)
        assert got.dtype == want.dtype == np.uint32
        assert got.shape == want.shape == (pair.PERIOD, 16)
        assert got.tobytes() == want.tobytes()
        assert got[pair.ROUNDS].tolist() == p2._permute_py(state)


def test_inputs_are_reduced_as_python_reduces_them():
    """Ints past p, negative ints and numpy lanes read as their residues
    on both paths; a state of another width is refused."""
    state = [bb.P + 5, -1, 2 * bb.P, 3**40] + list(range(12))
    lanes = np.arange(16, dtype=np.uint32) * np.uint32(123456789)
    for s in (state, lanes):
        assert p2.permute_ref(s) == p2._permute_py(s)
        assert pair.generate_trace(s).tobytes() == \
            pair._generate_trace_py(s).tobytes()
    with pytest.raises(ValueError):
        p2.permute_ref([0] * 15)


def _transfer_material():
    from ethrex_tpu.models import transfer_air as ta
    from ethrex_tpu.primitives.account import AccountState

    value, fee, tip = 1000, 21000 * 7, 21000 * 2
    tx = ta.TxSeg(bytes.fromhex("11" * 20), bytes.fromhex("22" * 20),
                  AccountState(nonce=4, balance=10**18),
                  AccountState(nonce=5, balance=10**18 - value - fee),
                  AccountState(nonce=1, balance=500),
                  AccountState(nonce=1, balance=500 + value),
                  value, fee, tip, r_created=False, r_noop=False)
    return ta.generate_transfer_trace([tx]), ta.transfer_public_inputs([tx])


def _state_update_material():
    from ethrex_tpu.models import state_update_air as sua
    from ethrex_tpu.stark import state_tree

    rng = np.random.default_rng(3)

    def word():
        return bytes(rng.integers(0, 256, 32, dtype=np.uint8))

    entries = {word(): word() for _ in range(4)}
    tree = state_tree.TouchedStateTree(entries, 2)
    r_pre = tree.root
    keys = list(entries)
    accesses = [tree.update(keys[int(rng.integers(0, len(keys)))], word())
                for _ in range(3)]
    return (sua.generate_state_update_trace(accesses, r_pre, 2, 8),
            sua.state_update_public_inputs(accesses, r_pre, tree.root, 8))


def _token_material():
    from ethrex_tpu.guest.transfer_log import TokSeg
    from ethrex_tpu.models import token_air as tka

    v1 = 12345
    kf = int.from_bytes(b"\x11" * 32, "big")
    kt = int.from_bytes(b"\x22" * 32, "big")
    segs = [TokSeg(v1, kf, 10**6, 10**6 - v1, kt, 500, 500 + v1),
            TokSeg(0, 0, 0, 0, 0, 0, 0, noop=True)]
    return tka.generate_token_trace(segs), tka.token_public_inputs(segs)


def _sponge_material():
    limbs = tpu_backend.binding_limbs(bytes(range(200)), [1] * 8, [2] * 8,
                                      [3] * 8, [4] * 8, None, [[5] * 8])
    return pair.generate_sponge_trace(limbs), pair.sponge_public_inputs(limbs)


@pytest.mark.parametrize("material", [
    _transfer_material, _state_update_material, _token_material,
    _sponge_material], ids=["TransferAir", "StateUpdateAir", "TokenAir",
                            "Poseidon2SpongeAir"])
def test_every_trace_and_its_publics_are_the_same_bytes_on_both_paths(
        material, monkeypatch):
    native_trace, native_pub = material()
    monkeypatch.setattr(p2, "_lib", False)
    py_trace, py_pub = material()
    assert native_trace.dtype == py_trace.dtype
    assert native_trace.shape == py_trace.shape
    assert native_trace.tobytes() == py_trace.tobytes()
    assert [int(v) for v in native_pub] == [int(v) for v in py_pub]


@pytest.mark.parametrize("case", ["no_compiler_output", "foreign_binary"])
def test_the_fallback_engages_when_the_library_cannot_load(
        case, tmp_path, monkeypatch):
    """No library and none can be built -> Python, and `available()`
    says so; a foreign binary over a good source is rebuilt once and
    loads."""
    so = tmp_path / "libposeidon2.so"
    if case == "no_compiler_output":
        monkeypatch.setattr(p2, "_SRC_PATH", str(tmp_path / "missing.c"))
    else:
        so.write_bytes(b"not an ELF object")
    monkeypatch.setattr(p2, "_SO_PATH", str(so))
    monkeypatch.setattr(p2, "_lib", None)
    assert p2.available() is (case == "foreign_binary")
    assert (p2._lib is False) is (case == "no_compiler_output")
    state = _states(7, 1)[0]
    assert p2.permute_ref(state) == p2._permute_py(state)
    assert pair.generate_trace(state).tobytes() == \
        pair._generate_trace_py(state).tobytes()


@pytest.mark.parametrize("path", ["native", "python"])
def test_trace_gen_span_says_which_permutation_ran(path, monkeypatch):
    if path == "python":
        monkeypatch.setattr(p2, "_lib", False)
    with tracing.trace_context(None) as trace_id:
        tpu_backend._traced_gen("Poseidon2SpongeAir",
                                pair.generate_sponge_trace, [7] * 8)
    spans = tracing.TRACER.get_trace(trace_id)["spans"]
    attrs = [s["attrs"] for s in spans if s["name"] == "prove.trace_gen"]
    assert attrs and attrs[0]["p2"] == path
    assert attrs[0]["rows"] == pair.PERIOD and attrs[0]["width"] == 24


def test_threads_share_the_engine_without_mixing_their_states():
    """The batch's jobs may generate traces on threads of their own:
    each call owns its buffers, the library only reads its constants."""
    states = _states(8, 64)
    want = [p2._permute_py(s) for s in states]
    got: dict = {}

    def worker(k):
        got[k] = [p2.permute_ref(s) for s in states[k::8] for _ in range(5)]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    for k in range(8):
        assert got[k] == [w for w in want[k::8] for _ in range(5)]
