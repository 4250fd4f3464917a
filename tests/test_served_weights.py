"""The Engine's served weights: each weight its programs read only through
a cast to the compute dtype is cast once, when the Engine is built.

Checked on the CPU at small widths, for a DeepSeek-shaped config (latent
attention, a leading dense layer, held and shared experts, packed FFNs)
and a smollm-shaped one (tied head): which leaves the rule casts, that
the chunk and decode logits and the greedy tokens equal the float32
master tree's to the last bit, that the compiled decode step is left no
float32 weight it only converts, and that a float32-compute config casts
nothing."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs import get_config
from repro.launch.hlo import parse_computations
from repro.launch.mesh import make_mesh
from repro.launch.serve import Engine
from repro.launch.served_weights import cast_only_inputs
from repro.obs import Telemetry
from repro.runtime.scheduler import Request

BF16 = jnp.bfloat16

CONFIGS = {
    "deepseek": lambda **kw: get_config("deepseek_v2_lite_16b").reduced(
        d_ff=64, head_pad=0, **kw),
    "smollm": lambda **kw: get_config("smollm-360m").reduced(head_pad=0,
                                                             **kw),
}


def _engine(name, **cfg_kw):
    tel = Telemetry.on(sparsity_every=0)
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = Engine(CONFIGS[name](**cfg_kw), mesh, max_seq=64, n_slots=3,
                 telemetry=tel, page_size=8, prefill_chunk=16)
    return eng, tel


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def served(request):
    return (request.param, *_engine(request.param))


# ---------------------------------------------------------------------------
# the rule, on small staged functions
# ---------------------------------------------------------------------------

def _scan_cast(w, x):
    def body(h, wi):
        return h @ wi.astype(BF16).astype(jnp.float32), None
    return lax.scan(body, x, w)[0]


def _scan_carry(w, x):
    def body(c, _):
        h, wc = c
        return (h @ wc.astype(BF16).astype(jnp.float32), wc), None
    return lax.scan(body, (x, w), None, length=2)[0][0]


def _scan_output(w, x):
    def body(h, wi):
        return h @ wi.astype(BF16).astype(jnp.float32), wi.reshape(16)
    return lax.scan(body, x, w)


RULE_CASES = {
    # a matmul weight read through its cast
    "cast": (lambda w, x: x @ w.astype(BF16).astype(jnp.float32), True),
    # an embedding table: gathered, then cast (a cast commutes with it)
    "gather_then_cast": (lambda w, x: jnp.take(
        w, jnp.array([0, 2]), axis=0).astype(BF16).astype(jnp.float32),
        True),
    "transpose_then_cast": (lambda w, x: x @ w.T.astype(BF16)
                            .astype(jnp.float32), True),
    # the per-layer slice of a scanned stack
    "scan_slice": (_scan_cast, True),
    "inside_jit": (lambda w, x: x @ jax.jit(lambda v: v.astype(BF16))(w)
                   .astype(jnp.float32), True),
    # read in float32 (a norm scale, a router)
    "read_f32": (lambda w, x: x * w[0], False),
    "cast_and_read": (lambda w, x: (x @ w.astype(BF16).astype(jnp.float32)
                                    + w[0]), False),
    "cast_to_other_dtype": (lambda w, x: x @ w.astype(jnp.float16)
                            .astype(jnp.float32), False),
    "unused": (lambda w, x: x * 2.0, False),
    # carried through the scan unchanged: staged as a constant of the body
    "scan_carry": (_scan_carry, True),
    # a scan's stacked output
    "scan_output": (_scan_output, False),
    "returned": (lambda w, x: (x, w), False),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_cast_only_inputs_rule(case):
    fn, want = RULE_CASES[case]
    w = jnp.ones((4, 4), jnp.float32)
    x = jnp.ones((3, 4), jnp.float32)
    if case in ("scan_slice", "scan_output"):
        w = jnp.ones((2, 4, 4), jnp.float32)
    jaxpr = jax.make_jaxpr(fn)(w, x)
    assert cast_only_inputs(jaxpr, 1, BF16) == [want]


# ---------------------------------------------------------------------------
# the Engine's served tree
# ---------------------------------------------------------------------------

def _read_in_f32(path: str) -> bool:
    """The leaves the step reads at float32: the router (scored in f32 at
    the highest precision), the norm scales, the latent norm."""
    return "router" in path or "scale" in path


def test_served_tree_casts_what_the_step_only_casts(served):
    name, eng, tel = served
    flat = jax.tree_util.tree_flatten_with_path(eng.params)[0]
    got = jax.tree.leaves(eng.served_params)
    cast = master = 0
    kinds = set()
    for (path, w), s in zip(flat, got):
        key = jax.tree_util.keystr(path)
        if w.dtype == jnp.int8:
            assert s is w, key                       # the routes
            kinds.add("route")
        elif _read_in_f32(key):
            assert s is w and s.dtype == jnp.float32, key
            kinds.add("f32")
        else:
            assert s.dtype == BF16, key
            np.testing.assert_array_equal(np.asarray(s),
                                          np.asarray(w.astype(BF16)))
            cast += s.size * 2
            master += w.size * 4
    assert kinds == {"route", "f32"}
    if name == "deepseek":
        keys = {jax.tree_util.keystr(p) for p, _ in flat}
        assert any("router" in k for k in keys)
        assert any("kv_norm" in k for k in keys)
        assert any("lead" in k for k in keys)
    gauges = tel.registry.snapshot()["gauges"]
    assert gauges["engine.weights.cast_once_bytes"] == cast > 0
    assert gauges["engine.weights.kept_bytes"] == master == 2 * cast


def test_f32_compute_casts_nothing():
    eng, tel = _engine("smollm", compute_dtype="float32")
    assert eng.served_params is eng.params
    gauges = tel.registry.snapshot()["gauges"]
    assert gauges["engine.weights.cast_once_bytes"] == 0
    assert gauges["engine.weights.kept_bytes"] == 0


def _chunk_then_decode(eng, params, seed):
    """Two prompt chunks of slot 0, then three decode steps of every
    slot, through the engine's own programs; every call's logits."""
    rng = np.random.default_rng(seed)
    geo = eng.kv_geo
    cache = eng.new_paged_cache()
    tables = geo.empty_tables(eng.n_slots)
    for s in range(eng.n_slots):
        geo.set_chain(tables, s, list(range(1 + 6 * s, 7 + 6 * s)))
    vocab = eng.cfg.vocab_size
    c = eng.prefill_chunk
    out = []
    for start, ln in ((0, c), (c, c - 5)):
        toks = rng.integers(0, vocab, (1, c)).astype(np.int32)
        logits, cache = eng._chunk_jit(params, cache, jnp.asarray(toks),
                                       jnp.asarray(tables[:1]),
                                       jnp.int32(start), jnp.int32(ln))
        out.append(np.asarray(logits))
    pos = np.array([2 * c - 5, 7, 30][:eng.n_slots], np.int32)
    for i in range(3):
        toks = rng.integers(0, vocab, (eng.n_slots, 1)).astype(np.int32)
        logits, cache, *_ = eng._step_paged(
            params, cache, {"tokens": jnp.asarray(toks)},
            jnp.asarray(pos + i), jnp.asarray(tables))
        out.append(np.asarray(logits))
    return out


def test_served_logits_equal_the_masters_to_the_bit(served):
    _, eng, _ = served
    got = _chunk_then_decode(eng, eng.served_params, 3)
    want = _chunk_then_decode(eng, eng.params, 3)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_served_greedy_tokens_equal_the_masters(name):
    rng = np.random.default_rng(5)
    reqs = lambda vocab: [Request(uid=i, prompt=rng.integers(
        0, vocab, n).tolist(), max_new_tokens=g)
        for i, (n, g) in enumerate(((21, 6), (5, 9), (33, 4), (12, 7)))]
    eng, _ = _engine(name)
    master, _ = _engine(name)
    master.served_params = master.params    # the step as it was
    assert jax.tree.leaves(eng.served_params)[0].dtype == BF16
    vocab = eng.cfg.vocab_size
    batch = reqs(vocab)
    got, _ = eng.serve(batch)
    want, _ = master.serve(batch)
    assert got == want


# ---------------------------------------------------------------------------
# the compiled decode step (CPU HLO)
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_LAYOUT_HLO = {"bitcast", "copy", "transpose", "reshape", "broadcast",
               "slice", "dynamic-slice", "gather"}


def _split_operands(s: str):
    """The operand names of ``op(...)`` and the text after it."""
    depth, i = 0, 0
    for i, ch in enumerate(s):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            break
    names = re.findall(r"%([\w.\-]+)", s[1:i])
    return names, s[i + 1:]


def _instructions(lines):
    """name -> (type, opcode, operand names, attributes, is_root)."""
    out = {}
    for line in lines:
        m = _INSTR.match(line)
        if not m:
            continue
        rest = m.group(2)
        if rest.startswith("("):                  # a tuple type
            depth = 0
            for j, ch in enumerate(rest):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    break
            typ, rest = rest[:j + 1], rest[j + 1:].lstrip()
        else:
            typ, _, rest = rest.partition(" ")
        op = re.match(r"([\w\-]+)", rest).group(1)
        operands, attrs = _split_operands(rest[len(op):])
        out[m.group(1)] = (typ, op, operands, attrs,
                           line.lstrip().startswith("ROOT"))
    return out


_PARAM = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=.*?\sparameter\((\d+)\)")


class _HloUses:
    """Is an HLO value only ever converted?  Follows fusions, calls,
    layout ops and the carried tuple of a ``while``, as
    ``served_weights`` follows the jaxpr."""

    def __init__(self, text):
        comps = parse_computations(text)
        self.comps = {k: _instructions(v) for k, v in comps.items()}
        self.params = {k: {int(m.group(2)): m.group(1)
                           for m in map(_PARAM.match, v) if m}
                       for k, v in comps.items()}
        self.bodies = {re.search(r"body=%([\w.\-]+)", a).group(1)
                       for c in self.comps.values()
                       for _, op, _, a, _ in c.values() if op == "while"}

    def cast_only(self, comp, name):
        """True when every use converts it, False when one reads it
        otherwise, None when nothing uses it."""
        instrs = self.comps[comp]
        if instrs[name][4]:
            return False                       # leaves the computation
        seen = None
        for user, (_, op, operands, attrs, root) in instrs.items():
            at = [i for i, o in enumerate(operands) if o == name]
            if not at:
                continue
            if op == "convert":
                r = True
            elif op in _LAYOUT_HLO:
                r = at == [0] and self.cast_only(comp, user)
            elif op in ("fusion", "call"):
                sub = re.search(r"(?:calls|to_apply)=%([\w.\-]+)",
                                attrs).group(1)
                r = self._all(sub, [self.params[sub][i] for i in at])
            elif op == "tuple" and root and comp in self.bodies:
                r = None           # carried to the next trip as it came
            elif op == "tuple":
                r = self._through_while(comp, user, at)
            else:
                r = False
            if r is False:
                return False
            seen = seen or r
        return seen

    def _all(self, comp, names):
        seen = None
        for n in names:
            r = self.cast_only(comp, n)
            if r is False:
                return False
            seen = seen or r
        return seen

    def _through_while(self, comp, tup, at):
        loops = [a for _, op, o, a, _ in self.comps[comp].values()
                 if op == "while" and o == [tup]]
        if len(loops) != 1:
            return False
        body = re.search(r"body=%([\w.\-]+)", loops[0]).group(1)
        arg = self.params[body][0]
        gtes = [n for n, (_, op, o, a, _) in self.comps[body].items()
                if op == "get-tuple-element" and o == [arg]
                and int(re.search(r"index=(\d+)", a).group(1)) in at]
        return self._all(body, gtes)


def _converted_f32_weights(text):
    """The float32 weight parameters of the entry computation that the
    program only ever converts."""
    uses = _HloUses(text)
    out = set()
    for line in parse_computations(text)["ENTRY"]:
        m = re.match(r"^\s*%([\w.\-]+)\s*=\s*f32\[.*?parameter\(\d+\).*"
                     r"op_name=\"(p\[[^\"]*)\"", line)
        if m and uses.cast_only("ENTRY", m.group(1)):
            out.add(m.group(2).replace("\\'", "'"))
    return out


def test_compiled_decode_step_converts_no_f32_weight(served):
    _, eng, _ = served
    served_text = eng.lower_decode_step().compile().as_text()
    assert _converted_f32_weights(served_text) == set()
    # the master tree's step: the weights it only converts are the ones
    # the served tree holds in bfloat16
    params = eng.served_params
    eng.served_params = eng.params
    try:
        master_text = eng.lower_decode_step().compile().as_text()
    finally:
        eng.served_params = params
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    cast = {"p" + jax.tree_util.keystr(p) for p, w in flat
            if w.dtype == BF16}
    assert _converted_f32_weights(master_text) == cast


def test_served_copies_keep_the_masters_sharding():
    """On a (1, 4) mesh each copy is laid out as its master leaf is."""
    mesh = make_mesh((1, 4), ("data", "model"))
    eng = Engine(CONFIGS["smollm"](), mesh, max_seq=32, n_slots=2)
    pairs = list(zip(jax.tree.leaves(eng.params),
                     jax.tree.leaves(eng.served_params)))
    assert any(s.dtype == BF16 for _, s in pairs)
    assert any(len(w.sharding.device_set) == 4 and
               not w.sharding.is_fully_replicated for w, _ in pairs)
    for w, s in pairs:
        assert s.sharding.is_equivalent_to(w.sharding, w.ndim)
