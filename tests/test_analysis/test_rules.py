"""graftlint rule-engine tests: per-rule positive + negative +
suppressed fixtures, jit-region discovery (decorators, call sites,
maker idiom, cross-module reachability through re-exports), and the
CLI's machine-parseable ``--json`` contract.

Every rule in analysis/rules.py has a POSITIVE fixture here proving it
fires — the acceptance contract: a rule that cannot fire is dead
weight, and a rule that fires on clean idioms would poison the
clean-tree gate (tests/test_lint_clean.py)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from differential_transformer_replication_tpu.analysis import (
    RULES,
    RULES_BY_ID,
    lint_paths,
)

REPO = Path(__file__).resolve().parents[2]
GRAFTLINT = REPO / "tools" / "graftlint.py"


def lint_src(tmp_path, src, filename="mod.py", rules=None):
    """Write one fixture module and lint the directory; returns the
    list of ACTIVE finding rule ids (sorted, duplicates kept)."""
    path = tmp_path / filename
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(src)
    result = lint_paths([str(tmp_path)], rules=rules)
    return result


def active_ids(result):
    return sorted(f.rule for f in result.active)


def all_ids(result):
    return sorted(f.rule for f in result.findings)


JIT_HEADER = "import jax\nimport jax.numpy as jnp\n"


class TestRuleCatalog:
    def test_at_least_eight_distinct_rules(self):
        assert len(RULES) >= 8
        assert len({r.id for r in RULES}) == len(RULES)

    def test_every_rule_documented(self):
        for r in RULES:
            assert r.summary and r.hint, f"{r.id} missing docs"


class TestGL101HostSync:
    def test_positive_item(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    v = jnp.sum(x)\n"
            "    return v.item()\n"
        ))
        assert "GL101" in active_ids(res)

    def test_positive_device_get(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    return jax.device_get(x)\n"
        ))
        assert "GL101" in active_ids(res)

    def test_positive_np_asarray_on_traced(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "import numpy as np\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    s = jnp.sum(x)\n"
            "    return np.asarray(s)\n"
        ))
        assert "GL101" in active_ids(res)

    def test_negative_outside_jit(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def host(x):\n"
            "    return x.item()\n"
        ))
        assert "GL101" not in active_ids(res)

    def test_negative_np_asarray_on_host_value(self, tmp_path):
        # np.asarray of an untraced (host) value in a jit region is a
        # trace-time constant, not a sync
        res = lint_src(tmp_path, JIT_HEADER + (
            "import numpy as np\n"
            "@jax.jit\n"
            "def f(x, lens):\n"
            "    table = np.asarray([1, 2, 3])\n"
            "    return x + table\n"
        ))
        assert "GL101" not in active_ids(res)

    def test_suppressed(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    v = jnp.sum(x)\n"
            "    return v.item()  # graftlint: disable=GL101\n"
        ))
        assert "GL101" not in active_ids(res)
        assert "GL101" in all_ids(res)  # reported, flagged suppressed


class TestGL102HostCast:
    def test_positive(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    s = jnp.sum(x)\n"
            "    return float(s)\n"
        ))
        assert "GL102" in active_ids(res)

    def test_negative_static_cast(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x, cfg_scale):\n"
            "    n = float(x.shape[0])\n"  # shapes are static
            "    return x * n\n"
        ))
        assert "GL102" not in active_ids(res)


class TestGL103ImpureCall:
    def test_positive_time(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "import time\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x * time.time()\n"
        ))
        assert "GL103" in active_ids(res)

    def test_positive_np_random(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "import numpy as np\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x + np.random.rand()\n"
        ))
        assert "GL103" in active_ids(res)

    def test_positive_print(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    print(x)\n"
            "    return x\n"
        ))
        assert "GL103" in active_ids(res)

    def test_negative_jax_random(self, tmp_path):
        # `from jax import random; random.normal(...)` is pure — the
        # alias must resolve to jax.random, not stdlib random
        res = lint_src(tmp_path, (
            "import jax\nfrom jax import random\n"
            "@jax.jit\n"
            "def f(key, x):\n"
            "    return x + random.normal(key, x.shape)\n"
        ))
        assert "GL103" not in active_ids(res)

    def test_negative_host_print(self, tmp_path):
        res = lint_src(tmp_path, (
            "def host():\n"
            "    print('hello')\n"
        ))
        assert "GL103" not in active_ids(res)


class TestGL104TracedBranch:
    def test_positive_if(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    s = jnp.sum(x)\n"
            "    if s > 0:\n"
            "        return x\n"
            "    return -x\n"
        ))
        assert "GL104" in active_ids(res)

    def test_positive_while(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    s = jnp.max(x)\n"
            "    while s > 0:\n"
            "        s = s - 1\n"
            "    return s\n"
        ))
        assert "GL104" in active_ids(res)

    def test_negative_static_config_branch(self, tmp_path):
        # branching on config/static values is the normal jit idiom
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x, n_micro=1):\n"
            "    if x.shape[0] == 1:\n"
            "        return x\n"
            "    return x * 2\n"
        ))
        assert "GL104" not in active_ids(res)

    def test_taint_propagates_through_arithmetic(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    s = jnp.sum(x)\n"
            "    t = s * 2 + 1\n"
            "    if t > 3:\n"
            "        return x\n"
            "    return -x\n"
        ))
        assert "GL104" in active_ids(res)

    def test_shape_access_strips_taint(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    h = jnp.reshape(x, (-1,))\n"
            "    if h.shape[0] > 4:\n"
            "        return h\n"
            "    return -h\n"
        ))
        assert "GL104" not in active_ids(res)

    def test_positive_branch_on_bare_parameter(self, tmp_path):
        # a jit root's params ARE the traced values — the canonical
        # hazard form must fire without any jnp call seeding taint
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def step_fn(x, y):\n"
            "    if x > 0:\n"
            "        return float(x)\n"
            "    return y\n"
        ))
        assert "GL104" in active_ids(res)
        assert "GL102" in active_ids(res)

    def test_positive_scan_body_param_while(self, tmp_path):
        # call-site roots (lax.scan body) get param seeding too
        res = lint_src(tmp_path, JIT_HEADER + (
            "def body(carry, t):\n"
            "    s = carry + t\n"
            "    while s > 0:\n"
            "        s = s - 1\n"
            "    return s, s\n"
            "out = jax.lax.scan(body, 0, None)\n"
        ))
        assert "GL104" in active_ids(res)

    def test_negative_attr_read_on_parameter(self, tmp_path):
        # config objects arrive as params; attribute reads on a bare
        # param stay static (if cfg.dropout > 0 is the normal idiom)
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x, cfg):\n"
            "    if cfg.dropout > 0:\n"
            "        return x * cfg.scale\n"
            "    return x\n"
        ))
        assert "GL104" not in active_ids(res)

    def test_negative_is_none_on_parameter(self, tmp_path):
        # identity tests never boolify a tracer
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x, mask):\n"
            "    if mask is not None:\n"
            "        x = x + mask\n"
            "    s = jnp.sum(x)\n"
            "    if s is None:\n"
            "        return x\n"
            "    return s\n"
        ))
        assert "GL104" not in active_ids(res)

    def test_negative_static_argnums_param(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "from functools import partial\n"
            "@partial(jax.jit, static_argnums=(1,))\n"
            "def f(x, n):\n"
            "    if n > 4:\n"
            "        return x * n\n"
            "    return x\n"
        ))
        assert "GL104" not in active_ids(res)

    def test_negative_static_argnames_call_site(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def f(x, n):\n"
            "    if n > 4:\n"
            "        return x * n\n"
            "    return x\n"
            "g = jax.jit(f, static_argnames=('n',))\n"
        ))
        assert "GL104" not in active_ids(res)

    def test_negative_helper_params_not_seeded(self, tmp_path):
        # transitively-reached helpers take host-static params (chunk
        # sizes, positions); only ROOT params are seeded
        res = lint_src(tmp_path, JIT_HEADER + (
            "def helper(x, chunk):\n"
            "    if chunk > 4:\n"
            "        return x[:chunk]\n"
            "    return x\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return helper(x, 8)\n"
        ))
        assert "GL104" not in active_ids(res)

    def test_param_rebound_to_host_value_drops_seed(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x, w):\n"
            "    w = 4\n"
            "    if w > 2:\n"
            "        return x * w\n"
            "    return x\n"
        ))
        assert "GL104" not in active_ids(res)


class TestGL105FString:
    def test_positive(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    s = jnp.max(x)\n"
            "    label = f'max={s}'\n"
            "    return x\n"
        ))
        assert "GL105" in active_ids(res)

    def test_negative_in_raise(self, tmp_path):
        # error messages at trace time run on static data — exempt
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    if x.shape[0] == 0:\n"
            "        raise ValueError(f'empty input {x.shape}')\n"
            "    return x\n"
        ))
        assert "GL105" not in active_ids(res)

    def test_negative_in_assert(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x, k):\n"
            "    assert x.shape[0] == k, f'bad shape {x.shape}'\n"
            "    return x\n"
        ))
        assert "GL105" not in active_ids(res)


class TestGL106SetIteration:
    def test_positive(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(params):\n"
            "    total = 0.0\n"
            "    for k in {'wq', 'wk', 'wv'}:\n"
            "        total = total + jnp.sum(params[k])\n"
            "    return total\n"
        ))
        assert "GL106" in active_ids(res)

    def test_positive_comprehension(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(params):\n"
            "    vals = [params[k] for k in {'a', 'b'}]\n"
            "    return vals[0]\n"
        ))
        assert "GL106" in active_ids(res)

    def test_negative_sorted_iteration(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(params):\n"
            "    total = 0.0\n"
            "    for k in ('wq', 'wk', 'wv'):\n"
            "        total = total + jnp.sum(params[k])\n"
            "    return total\n"
        ))
        assert "GL106" not in active_ids(res)


class TestGL107GlobalState:
    def test_positive(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "_cache = None\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    global _cache\n"
            "    _cache = x\n"
            "    return x\n"
        ))
        assert "GL107" in active_ids(res)

    def test_negative_host_global(self, tmp_path):
        res = lint_src(tmp_path, (
            "_cache = None\n"
            "def host(x):\n"
            "    global _cache\n"
            "    _cache = x\n"
        ))
        assert "GL107" not in active_ids(res)


class TestGL201MissingDonate:
    def test_positive_call_form(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def train_step(state, batch):\n"
            "    return state\n"
            "jitted = jax.jit(train_step)\n"
        ))
        assert "GL201" in active_ids(res)

    def test_positive_decorator_form(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def decode_step(pool, tokens):\n"
            "    return pool\n"
        ))
        assert "GL201" in active_ids(res)

    def test_negative_with_donate(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "from functools import partial\n"
            "def train_step(state, batch):\n"
            "    return state\n"
            "jitted = jax.jit(train_step, donate_argnums=(0,))\n"
            "@partial(jax.jit, donate_argnums=(0,))\n"
            "def update_step(state):\n"
            "    return state\n"
        ))
        assert "GL201" not in active_ids(res)

    def test_negative_eval_exempt(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def eval_step(params, x):\n"
            "    return params\n"
        ))
        assert "GL201" not in active_ids(res)

    def test_negative_maker_call_with_donate(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def make_step_fn(cfg):\n"
            "    def step(state, batch):\n"
            "        return state\n"
            "    return step\n"
            "jitted = jax.jit(make_step_fn(None), donate_argnums=(0,))\n"
        ))
        assert "GL201" not in active_ids(res)


class TestGL202SyncInStepLoop:
    def test_positive(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def train(step, state, batch):\n"
            "    for i in range(100):\n"
            "        state, metrics = step(state, batch)\n"
            "        loss = float(metrics['loss'])\n"
            "    return loss\n"
        ))
        assert "GL202" in active_ids(res)

    def test_positive_device_get(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def train(train_step, state, batch):\n"
            "    while True:\n"
            "        state, metrics = train_step(state, batch)\n"
            "        m = jax.device_get(metrics)\n"
        ))
        assert "GL202" in active_ids(res)

    def test_negative_outside_loop(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def train(step, state, batch):\n"
            "    for i in range(100):\n"
            "        state, metrics = step(state, batch)\n"
            "    return float(metrics['loss'])\n"
        ))
        assert "GL202" not in active_ids(res)

    def test_negative_loop_without_step(self, tmp_path):
        res = lint_src(tmp_path, (
            "def tally(xs):\n"
            "    total = 0.0\n"
            "    for x in xs:\n"
            "        total += float(x)\n"
            "    return total\n"
        ))
        assert "GL202" not in active_ids(res)

    def test_suppressed_with_trailing_why(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def train(step, state, batch):\n"
            "    for i in range(100):\n"
            "        state, metrics = step(state, batch)\n"
            "        if i % 50 == 0:\n"
            "            loss = float(metrics['loss'])  "
            "# graftlint: disable=GL202 (log-boundary sync)\n"
        ))
        assert "GL202" not in active_ids(res)
        assert "GL202" in all_ids(res)


class TestGL301LockDiscipline:
    POS = (
        "import threading\n"
        "class Runner:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.count = 0\n"
        "    def bump(self):\n"
        "        self.count += 1\n"
        "    def read(self):\n"
        "        with self._lock:\n"
        "            return self.count\n"
    )

    def test_positive_in_serving_dir(self, tmp_path):
        res = lint_src(tmp_path, self.POS, filename="serving/runner.py")
        assert "GL301" in active_ids(res)

    def test_negative_outside_serving(self, tmp_path):
        res = lint_src(tmp_path, self.POS, filename="train/runner.py")
        assert "GL301" not in active_ids(res)

    def test_positive_direct_file_invocation(self, tmp_path):
        # spot-linting ONE serving file must apply the same rules as
        # linting the directory (file args keep one parent component)
        path = tmp_path / "serving" / "runner.py"
        path.parent.mkdir(parents=True)
        path.write_text(self.POS)
        res = lint_paths([str(path)])
        assert "GL301" in active_ids(res)

    def test_negative_checkout_under_serving_parent(self, tmp_path):
        # a repo cloned at /somewhere/serving/repo must NOT have the
        # serving-only rule applied to its whole tree — membership is
        # lint-root-relative, never absolute
        root = tmp_path / "serving" / "repo"
        (root / "train").mkdir(parents=True)
        (root / "train" / "runner.py").write_text(self.POS)
        res = lint_paths([str(root)])
        assert "GL301" not in active_ids(res)

    def test_negative_guarded_write(self, tmp_path):
        res = lint_src(tmp_path, (
            "import threading\n"
            "class Runner:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.count = 0\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self.count += 1\n"
            "    def read(self):\n"
            "        with self._lock:\n"
            "            return self.count\n"
        ), filename="serving/runner.py")
        assert "GL301" not in active_ids(res)

    def test_negative_lockless_class_exempt(self, tmp_path):
        # classes that own no lock are single-threaded by design here
        res = lint_src(tmp_path, (
            "class Plain:\n"
            "    def __init__(self):\n"
            "        self.count = 0\n"
            "    def bump(self):\n"
            "        self.count += 1\n"
        ), filename="serving/plain.py")
        assert "GL301" not in active_ids(res)

    def test_threadsafe_alias_suppression(self, tmp_path):
        res = lint_src(tmp_path, (
            "import threading\n"
            "class Runner:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.count = 0\n"
            "    def bump(self):\n"
            "        self.count += 1  # graftlint: threadsafe (GIL pub)\n"
            "    def read(self):\n"
            "        with self._lock:\n"
            "            return self.count\n"
        ), filename="serving/runner.py")
        assert "GL301" not in active_ids(res)
        assert "GL301" in all_ids(res)


class TestJitRegionDiscovery:
    def test_call_site_transform_marks_root(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def body(x):\n"
            "    return x.item()\n"
            "jitted = jax.jit(body)\n"
        ))
        assert "GL101" in active_ids(res)

    def test_lax_scan_body_is_jit_region(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "from jax import lax\n"
            "def outer(xs):\n"
            "    def body(carry, x):\n"
            "        return carry, x.item()\n"
            "    return lax.scan(body, 0.0, xs)\n"
        ))
        assert "GL101" in active_ids(res)

    def test_maker_idiom_marks_returned_fn(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def make_step(cfg):\n"
            "    def step(state, batch):\n"
            "        s = jnp.sum(batch)\n"
            "        return state, float(s)\n"
            "    return step\n"
            "jitted = jax.jit(make_step(None), donate_argnums=(0,))\n"
        ))
        assert "GL102" in active_ids(res)

    def test_callee_reached_through_call_graph(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def helper(x):\n"
            "    return x.item()\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return helper(x)\n"
        ))
        assert "GL101" in active_ids(res)

    def test_cross_module_reachability(self, tmp_path):
        (tmp_path / "impl.py").write_text(
            "def deep_helper(x):\n"
            "    return x.item()\n"
        )
        res = lint_src(tmp_path, (
            "import jax\n"
            "from impl import deep_helper\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return deep_helper(x)\n"
        ), filename="main.py")
        assert "GL101" in active_ids(res)
        # the finding lands in the CALLEE's file
        f = next(x for x in res.active if x.rule == "GL101")
        assert f.path.endswith("impl.py")

    @pytest.mark.parametrize("entry", [
        "helper", "functools.partial(helper, k=1)", "make()"])
    def test_callee_reached_through_a_module_level_table(self, tmp_path,
                                                         entry):
        """Dispatch through a table of functions (models/decode.py
        ``KINDS[kind].step``): whoever reads the table may run what it
        mentions, a ``partial`` handed on as a call argument included."""
        res = lint_src(tmp_path, JIT_HEADER + (
            "import functools\n"
            "def helper(x, k=0):\n"
            "    return x.item()\n"
            "def make():\n"
            "    return dict(step=functools.partial(helper, k=1))\n"
            f"TABLE = {{'a': {entry}}}\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return TABLE['a'](x)\n"
        ))
        assert "GL101" in active_ids(res)

    def test_unreached_helper_is_host_code(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def helper(x):\n"
            "    return x.item()\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x * 2\n"
        ))
        assert "GL101" not in active_ids(res)


class TestSuppressionSyntax:
    def test_disable_file(self, tmp_path):
        res = lint_src(tmp_path, (
            "# graftlint: disable-file=GL101\n"
        ) + JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    return x.item()\n"
        ))
        assert "GL101" not in active_ids(res)
        assert "GL101" in all_ids(res)

    def test_disable_file_all(self, tmp_path):
        res = lint_src(tmp_path, (
            "# graftlint: disable-file\n"
        ) + JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    print(x)\n"
            "    return x.item()\n"
        ))
        assert not active_ids(res)

    def test_rule_name_token(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    return x.item()  # graftlint: disable=host-sync-in-jit\n"
        ))
        assert "GL101" not in active_ids(res)

    def test_unknown_rule_token_is_inert(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    return x.item()  # graftlint: disable=GL999\n"
        ))
        assert "GL101" in active_ids(res)

    def test_multiline_statement_suppressed_from_first_line(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    v = jax.device_get(  # graftlint: disable=GL101\n"
            "        x\n"
            "    )\n"
            "    return v\n"
        ))
        assert "GL101" not in active_ids(res)


class TestRuleFilter:
    def test_rules_option_limits_scope(self, tmp_path):
        src = JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    print(x)\n"
            "    return x.item()\n"
        )
        res = lint_src(tmp_path, src, rules=["GL103"])
        assert "GL103" in active_ids(res)
        assert "GL101" not in active_ids(res)


class TestSameBasenameArgs:
    def test_both_colliding_files_are_linted(self, tmp_path):
        # `graftlint a/util.py b/util.py` must scan BOTH (the old
        # last-writer-wins keying made the exit code order-dependent)
        bad = JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    return x.item()\n"
        )
        clean = "def ok():\n    return 1\n"
        (tmp_path / "a").mkdir()
        (tmp_path / "b").mkdir()
        (tmp_path / "a" / "util.py").write_text(bad)
        (tmp_path / "b" / "util.py").write_text(clean)
        for order in (
            [tmp_path / "a" / "util.py", tmp_path / "b" / "util.py"],
            [tmp_path / "b" / "util.py", tmp_path / "a" / "util.py"],
        ):
            res = lint_paths([str(p) for p in order])
            assert res.files_scanned == 2
            assert "GL101" in active_ids(res), order

    def test_colliding_files_keep_their_own_suppression_spans(self, tmp_path):
        # both args display as serving/x.py; the statement-span cache
        # must stay per-FILE or one file's multi-line suppression is
        # checked against the other's statement extents
        plain = JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    return x.item()\n"
        )
        suppressed = JIT_HEADER + (
            "@jax.jit\n"
            "def g(y):\n"
            "    v = (\n"
            "        y.item()\n"
            "    )  # graftlint: disable=GL101 (fixture)\n"
            "    return v\n"
        )
        (tmp_path / "a" / "serving").mkdir(parents=True)
        (tmp_path / "b" / "serving").mkdir(parents=True)
        (tmp_path / "a" / "serving" / "x.py").write_text(plain)
        (tmp_path / "b" / "serving" / "x.py").write_text(suppressed)
        res = lint_paths([
            str(tmp_path / "a" / "serving" / "x.py"),
            str(tmp_path / "b" / "serving" / "x.py"),
        ])
        gl101 = [f for f in res.findings if f.rule == "GL101"]
        assert [f.suppressed for f in gl101] == [False, True]


SHARD_HEADER = (
    "import jax\nimport jax.numpy as jnp\n"
    "from jax.experimental.shard_map import shard_map\n"
)

PALLAS_HEADER = (
    "import jax\nimport jax.numpy as jnp\n"
    "from jax.experimental import pallas as pl\n"
    "from jax.experimental.pallas import tpu as pltpu\n"
)


class TestGL401UnboundCollective:
    def test_positive_no_binding_context(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def helper(x):\n"
            "    return jax.lax.psum(x, 'data')\n"
        ))
        assert "GL401" in active_ids(res)

    def test_positive_plain_jit_region(self, tmp_path):
        # jitted but NOT shard_mapped: the axis name is unbound at trace
        res = lint_src(tmp_path, JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    return jax.lax.pmean(x, 'data')\n"
        ))
        assert "GL401" in active_ids(res)

    def test_negative_direct_shard_map_body(self, tmp_path):
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def body(x):\n"
            "    return jax.lax.psum(x, 'data')\n"
            "f = shard_map(body, mesh=None, in_specs=None, out_specs=None)\n"
        ))
        assert "GL401" not in active_ids(res)

    def test_negative_axis_index_in_body(self, tmp_path):
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def body(x):\n"
            "    return x + jax.lax.axis_index('data')\n"
            "f = shard_map(body, mesh=None, in_specs=None, out_specs=None)\n"
        ))
        assert "GL401" not in active_ids(res)

    def test_positive_pmap_wrong_axis(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def body(x):\n"
            "    return jax.lax.psum(x, 'model')\n"
            "f = jax.pmap(body, axis_name='data')\n"
        ))
        assert "GL401" in active_ids(res)

    def test_negative_pmap_right_axis(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def body(x):\n"
            "    return jax.lax.psum(x, 'data')\n"
            "f = jax.pmap(body, axis_name='data')\n"
        ))
        assert "GL401" not in active_ids(res)

    def test_negative_variable_axis_under_binder(self, tmp_path):
        # axis threaded in as a variable: bound by construction
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def make(axis):\n"
            "    def body(x):\n"
            "        return jax.lax.pmean(x, axis)\n"
            "    return body\n"
            "def build(mesh):\n"
            "    return shard_map(make('data'), mesh=mesh, in_specs=None,\n"
            "                     out_specs=None)\n"
        ))
        assert "GL401" not in active_ids(res)

    def test_negative_wrapper_idiom(self, tmp_path):
        # body reaches shard_map only through a wrapper's parameter —
        # (a project-local shard_map wrapper)
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def wrapper(fn, mesh):\n"
            "    return shard_map(fn, mesh=mesh, in_specs=None,\n"
            "                     out_specs=None)\n"
            "def body(x):\n"
            "    return jax.lax.pmean(x, 'data')\n"
            "def caller(mesh, x):\n"
            "    return wrapper(body, mesh)(x)\n"
        ))
        assert "GL401" not in active_ids(res)

    def test_negative_param_bound_lambda(self, tmp_path):
        # the dp_step shape: a pmean lambda handed into a maker whose
        # returned step runs under shard_map
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def make_step(cfg, loss_sync=None):\n"
            "    def step(state, batch):\n"
            "        loss = jnp.sum(batch)\n"
            "        if loss_sync is not None:\n"
            "            loss = loss_sync(loss)\n"
            "        return state, loss\n"
            "    return step\n"
            "def build(cfg, mesh):\n"
            "    axis = 'data'\n"
            "    inner = make_step(cfg,\n"
            "                      loss_sync=lambda l: jax.lax.pmean(l, axis))\n"
            "    def raw(state, batch):\n"
            "        return inner(state, batch)\n"
            "    return shard_map(raw, mesh=mesh, in_specs=None,\n"
            "                     out_specs=None)\n"
        ))
        assert "GL401" not in active_ids(res)

    def test_negative_defvjp_backward(self, tmp_path):
        # a custom-vjp backward pmean is bound through the primal's
        # reachability (the _bucket_sync shape)
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def make_sync(axis):\n"
            "    @jax.custom_vjp\n"
            "    def sync(t):\n"
            "        return t\n"
            "    def fwd(t):\n"
            "        return t, None\n"
            "    def bwd(_, ct):\n"
            "        return (jax.lax.pmean(ct, axis),)\n"
            "    sync.defvjp(fwd, bwd)\n"
            "    return sync\n"
            "def build(mesh):\n"
            "    sync = make_sync('data')\n"
            "    def body(x):\n"
            "        return sync(x)\n"
            "    return shard_map(body, mesh=mesh, in_specs=None,\n"
            "                     out_specs=None)\n"
        ))
        assert "GL401" not in active_ids(res)

    def test_positive_axis_kwarg_does_not_mask_name(self, tmp_path):
        # all_gather's `axis=` kwarg is the ARRAY dimension, not the
        # axis name — it must not clobber the positional name candidate
        res = lint_src(tmp_path, JIT_HEADER + (
            "def body(x):\n"
            "    return jax.lax.all_gather(x, 'mp', axis=0)\n"
            "f = jax.pmap(body, axis_name='dp')\n"
        ))
        assert "GL401" in active_ids(res)

    def test_suppressed(self, tmp_path):
        res = lint_src(tmp_path, JIT_HEADER + (
            "def helper(x):\n"
            "    return jax.lax.psum(x, 'data')  "
            "# graftlint: disable=GL401 (fixture)\n"
        ))
        assert "GL401" not in active_ids(res)
        assert "GL401" in all_ids(res)


class TestGL402CollectiveUnderBranch:
    def test_positive_cond_arm(self, tmp_path):
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def body(x, pred):\n"
            "    def yes(v):\n"
            "        return jax.lax.psum(v, 'data')\n"
            "    def no(v):\n"
            "        return v\n"
            "    return jax.lax.cond(pred, yes, no, x)\n"
            "f = shard_map(body, mesh=None, in_specs=None, out_specs=None)\n"
        ))
        assert "GL402" in active_ids(res)

    def test_positive_while_body(self, tmp_path):
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def body(x):\n"
            "    def cond_fn(c):\n"
            "        return c[1] > 0\n"
            "    def body_fn(c):\n"
            "        return (jax.lax.pmean(c[0], 'data'), c[1] - 1)\n"
            "    return jax.lax.while_loop(cond_fn, body_fn, (x, 3))\n"
            "f = shard_map(body, mesh=None, in_specs=None, out_specs=None)\n"
        ))
        assert "GL402" in active_ids(res)

    def test_positive_transitively_reached(self, tmp_path):
        # the collective hides one call deep inside the arm
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def deep(v):\n"
            "    return jax.lax.psum(v, 'data')\n"
            "def body(x, pred):\n"
            "    def yes(v):\n"
            "        return deep(v)\n"
            "    return jax.lax.cond(pred, yes, lambda v: v, x)\n"
            "f = shard_map(body, mesh=None, in_specs=None, out_specs=None)\n"
        ))
        assert "GL402" in active_ids(res)

    def test_negative_collective_outside_arm(self, tmp_path):
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def body(x, pred):\n"
            "    s = jax.lax.psum(x, 'data')\n"
            "    return jax.lax.cond(pred, lambda v: v, lambda v: -v, s)\n"
            "f = shard_map(body, mesh=None, in_specs=None, out_specs=None)\n"
        ))
        assert "GL402" not in active_ids(res)

    def test_negative_scan_body_is_uniform(self, tmp_path):
        # scan/fori_loop trip counts are static — every shard runs the
        # same number of collectives (the ring-attention shape)
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def body(ks):\n"
            "    def step(c, x):\n"
            "        return jax.lax.ppermute(c, 'sequence',\n"
            "                                [(0, 1), (1, 0)]), None\n"
            "    out, _ = jax.lax.scan(step, ks, None, length=4)\n"
            "    return out\n"
            "f = shard_map(body, mesh=None, in_specs=None, out_specs=None)\n"
        ))
        assert "GL402" not in active_ids(res)

    def test_suppressed(self, tmp_path):
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def body(x, pred):\n"
            "    def yes(v):\n"
            "        return jax.lax.psum(v, 'data')  "
            "# graftlint: disable=GL402 (pred is pmean-uniform)\n"
            "    return jax.lax.cond(pred, yes, lambda v: v, x)\n"
            "f = shard_map(body, mesh=None, in_specs=None, out_specs=None)\n"
        ))
        assert "GL402" not in active_ids(res)
        assert "GL402" in all_ids(res)


class TestGL403HostTransferInShardBody:
    def test_positive(self, tmp_path):
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def body(x):\n"
            "    return jax.device_put(x)\n"
            "f = shard_map(body, mesh=None, in_specs=None, out_specs=None)\n"
        ))
        assert "GL403" in active_ids(res)

    def test_negative_host_device_put(self, tmp_path):
        # placement BEFORE the shard_map call is the correct idiom
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def body(x):\n"
            "    return x * 2\n"
            "def launch(mesh, x, sharding):\n"
            "    x = jax.device_put(x, sharding)\n"
            "    f = shard_map(body, mesh=mesh, in_specs=None,\n"
            "                  out_specs=None)\n"
            "    return f(x)\n"
        ))
        assert "GL403" not in active_ids(res)

    def test_suppressed(self, tmp_path):
        res = lint_src(tmp_path, SHARD_HEADER + (
            "def body(x):\n"
            "    return jax.device_put(x)  "
            "# graftlint: disable=GL403 (fixture)\n"
            "f = shard_map(body, mesh=None, in_specs=None, out_specs=None)\n"
        ))
        assert "GL403" not in active_ids(res)
        assert "GL403" in all_ids(res)


class TestGL501GridMismatch:
    POS = PALLAS_HEADER + (
        "def kern(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...]\n"
        "def call(x):\n"
        "    return pl.pallas_call(\n"
        "        kern,\n"
        "        grid=(3,),\n"
        "        in_specs=[pl.BlockSpec((48, 128), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((48, 128), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((100, 128), jnp.float32),\n"
        "    )(x)\n"
    )

    def test_positive(self, tmp_path):
        res = lint_src(tmp_path, self.POS)
        assert "GL501" in active_ids(res)

    def test_positive_through_module_constants(self, tmp_path):
        res = lint_src(tmp_path, PALLAS_HEADER + (
            "_ROWS = 100\n"
            "_BLOCK = 48\n"
            "def kern(x_ref, o_ref):\n"
            "    o_ref[...] = x_ref[...]\n"
            "def call(x):\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        out_specs=pl.BlockSpec((_BLOCK, 128),\n"
            "                               lambda i: (i, 0)),\n"
            "        out_shape=jax.ShapeDtypeStruct((_ROWS, 128),\n"
            "                                       jnp.float32),\n"
            "    )(x)\n"
        ))
        assert "GL501" in active_ids(res)

    def test_negative_divisible(self, tmp_path):
        res = lint_src(tmp_path, self.POS.replace("(100, 128)", "(96, 128)"))
        assert "GL501" not in active_ids(res)

    def test_negative_dynamic_shapes(self, tmp_path):
        # non-static dims: a prover stays silent, never guesses
        res = lint_src(tmp_path, self.POS.replace(
            "def call(x):", "def call(x, M):"
        ).replace("(100, 128)", "(M, 128)"))
        assert "GL501" not in active_ids(res)

    def test_negative_nested_scope_constant_does_not_leak(self, tmp_path):
        # a sibling nested helper's local `BM = 100` is NOT the call
        # site's BM (module-level BM = 64 divides 256 evenly)
        res = lint_src(tmp_path, PALLAS_HEADER + (
            "BM = 64\n"
            "def kern(x_ref, o_ref):\n"
            "    o_ref[...] = x_ref[...]\n"
            "def call(x):\n"
            "    def helper():\n"
            "        BM = 100\n"
            "        return BM\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        out_specs=pl.BlockSpec((BM, 128), lambda i: (i, 0)),\n"
            "        out_shape=jax.ShapeDtypeStruct((256, 128),\n"
            "                                       jnp.float32),\n"
            "    )(x)\n"
        ))
        assert "GL501" not in active_ids(res)

    def test_suppressed(self, tmp_path):
        res = lint_src(tmp_path, self.POS.replace(
            "        out_specs=pl.BlockSpec((48, 128), lambda i: (i, 0)),\n",
            "        out_specs=pl.BlockSpec((48, 128), lambda i: (i, 0)),  "
            "# graftlint: disable=GL501 (fixture)\n",
        ))
        assert "GL501" not in active_ids(res)
        assert "GL501" in all_ids(res)


class TestGL502SubFp32Accumulator:
    POS = PALLAS_HEADER + (
        "def kern(x_ref, o_ref, acc_ref):\n"
        "    acc_ref[...] += x_ref[...] * 2.0\n"
        "    o_ref[...] = acc_ref[...].astype(o_ref.dtype)\n"
        "def call(x, M):\n"
        "    return pl.pallas_call(\n"
        "        kern,\n"
        "        grid=(4,),\n"
        "        in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((M, 128), jnp.float32),\n"
        "        scratch_shapes=[pltpu.VMEM((128, 128), jnp.bfloat16)],\n"
        "    )(x)\n"
    )

    def test_positive(self, tmp_path):
        res = lint_src(tmp_path, self.POS)
        assert "GL502" in active_ids(res)

    def test_positive_star_refs_unpack(self, tmp_path):
        # the house kernel style: *refs + tuple unpack in the body
        res = lint_src(tmp_path, PALLAS_HEADER + (
            "def kern(*refs):\n"
            "    x_ref, o_ref, acc_ref = refs\n"
            "    acc_ref[...] = acc_ref[...] + x_ref[...] * 2.0\n"
            "    o_ref[...] = acc_ref[...].astype(o_ref.dtype)\n"
            "def call(x, M):\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],\n"
            "        out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),\n"
            "        out_shape=jax.ShapeDtypeStruct((M, 128), jnp.float32),\n"
            "        scratch_shapes=[pltpu.VMEM((128, 128), jnp.bfloat16)],\n"
            "    )(x)\n"
        ))
        assert "GL502" in active_ids(res)

    def test_negative_fp32_scratch(self, tmp_path):
        res = lint_src(tmp_path, self.POS.replace("jnp.bfloat16", "jnp.float32"))
        assert "GL502" not in active_ids(res)

    def test_negative_bf16_scratch_without_accumulation(self, tmp_path):
        # sub-fp32 scratch used as a plain store target is legitimate
        res = lint_src(tmp_path, self.POS.replace(
            "    acc_ref[...] += x_ref[...] * 2.0\n",
            "    acc_ref[...] = x_ref[...] * 2.0\n",
        ))
        assert "GL502" not in active_ids(res)

    def test_suppressed(self, tmp_path):
        res = lint_src(tmp_path, self.POS.replace(
            "    acc_ref[...] += x_ref[...] * 2.0\n",
            "    acc_ref[...] += x_ref[...] * 2.0  "
            "# graftlint: disable=GL502 (fixture)\n",
        ))
        assert "GL502" not in active_ids(res)
        assert "GL502" in all_ids(res)


class TestGL503VmemBudget:
    POS = PALLAS_HEADER + (
        "def kern(x_ref, o_ref, acc_ref):\n"
        "    o_ref[...] = x_ref[...]\n"
        "def call(x, M):\n"
        "    return pl.pallas_call(\n"
        "        kern,\n"
        "        out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((M, 128), jnp.float32),\n"
        "        scratch_shapes=[pltpu.VMEM((2048, 4096), jnp.float32)],\n"
        "    )(x)\n"
    )

    def test_positive_is_warning(self, tmp_path):
        res = lint_src(tmp_path, self.POS)
        hits = [f for f in res.active if f.rule == "GL503"]
        assert hits and all(f.severity == "warning" for f in hits)
        # warn-severity findings never gate
        assert not res.gating

    def test_budget_configurable(self, tmp_path):
        from differential_transformer_replication_tpu.analysis.lint import (
            lint_paths as lp,
        )
        path = tmp_path / "mod.py"
        path.write_text(self.POS)
        res = lp([str(tmp_path)], vmem_budget_mib=64.0)
        assert "GL503" not in active_ids(res)

    def test_negative_small_blocks(self, tmp_path):
        res = lint_src(tmp_path, self.POS.replace("(2048, 4096)", "(128, 128)"))
        assert "GL503" not in active_ids(res)

    def test_suppressed(self, tmp_path):
        res = lint_src(tmp_path, self.POS.replace(
            "    return pl.pallas_call(\n",
            "    return pl.pallas_call(  "
            "# graftlint: disable=GL503 (fixture)\n",
        ))
        assert "GL503" not in active_ids(res)
        assert "GL503" in all_ids(res)


class TestGL504ImpureKernel:
    def test_positive_impure_call(self, tmp_path):
        res = lint_src(tmp_path, PALLAS_HEADER + (
            "import time\n"
            "def kern(x_ref, o_ref):\n"
            "    o_ref[...] = x_ref[...] * time.time()\n"
            "def call(x):\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),\n"
            "    )(x)\n"
        ))
        ids = active_ids(res)
        assert "GL504" in ids
        assert "GL103" not in ids  # kernel impurity is GL504, not GL103

    def test_positive_impure_call_site_inside_jit_region(self, tmp_path):
        # the common real shape: the pallas_call SITE is itself jitted.
        # Kernel-ness must win — regular jit reachability stops at the
        # kernel, so the impure call reports GL504, not GL103
        res = lint_src(tmp_path, PALLAS_HEADER + (
            "import time\n"
            "def kern(x_ref, o_ref):\n"
            "    o_ref[...] = x_ref[...] * time.time()\n"
            "@jax.jit\n"
            "def call(x):\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),\n"
            "    )(x)\n"
        ))
        ids = active_ids(res)
        assert "GL504" in ids
        assert "GL103" not in ids

    def test_positive_closure_over_traced(self, tmp_path):
        res = lint_src(tmp_path, PALLAS_HEADER + (
            "def call(x):\n"
            "    y = jnp.sum(x)\n"
            "    def kern(x_ref, o_ref):\n"
            "        o_ref[...] = x_ref[...] + y\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),\n"
            "    )(x)\n"
        ))
        assert "GL504" in active_ids(res)

    def test_positive_index_map_closure(self, tmp_path):
        res = lint_src(tmp_path, PALLAS_HEADER + (
            "def kern(x_ref, o_ref):\n"
            "    o_ref[...] = x_ref[...]\n"
            "def call(x):\n"
            "    off = jnp.argmax(x)\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        in_specs=[pl.BlockSpec((8, 128),\n"
            "                               lambda i: (i + off, 0))],\n"
            "        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),\n"
            "        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),\n"
            "    )(x)\n"
        ))
        assert "GL504" in active_ids(res)

    def test_negative_static_closure(self, tmp_path):
        # closing over shapes/ints from the enclosing scope is the
        # normal kernel idiom (block sizes, head counts)
        res = lint_src(tmp_path, PALLAS_HEADER + (
            "def call(x, block):\n"
            "    S, d = x.shape\n"
            "    def kern(x_ref, o_ref):\n"
            "        o_ref[...] = x_ref[...] * S\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        in_specs=[pl.BlockSpec((block, d),\n"
            "                               lambda i: (i, 0))],\n"
            "        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),\n"
            "        out_shape=jax.ShapeDtypeStruct((S, d), x.dtype),\n"
            "    )(x)\n"
        ))
        assert "GL504" not in active_ids(res)

    def test_negative_partial_bound_static(self, tmp_path):
        res = lint_src(tmp_path, PALLAS_HEADER + (
            "import functools\n"
            "def kern(x_ref, o_ref, *, scale):\n"
            "    o_ref[...] = x_ref[...] * scale\n"
            "def call(x):\n"
            "    return pl.pallas_call(\n"
            "        functools.partial(kern, scale=2.0),\n"
            "        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),\n"
            "    )(x)\n"
        ))
        assert "GL504" not in active_ids(res)

    def test_suppressed(self, tmp_path):
        res = lint_src(tmp_path, PALLAS_HEADER + (
            "import time\n"
            "def kern(x_ref, o_ref):\n"
            "    o_ref[...] = x_ref[...] * time.time()  "
            "# graftlint: disable=GL504 (fixture)\n"
            "def call(x):\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),\n"
            "    )(x)\n"
        ))
        assert "GL504" not in active_ids(res)
        assert "GL504" in all_ids(res)


LOCKS_HEADER = "import threading\nimport queue\nimport time\n"


class TestGL601LockOrderInversion:
    POS = LOCKS_HEADER + (
        "class R:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def one(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def two(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n"
    )

    def test_positive_direct(self, tmp_path):
        res = lint_src(tmp_path, self.POS)
        assert "GL601" in active_ids(res)

    def test_positive_across_two_methods_via_call(self, tmp_path):
        # A->B through a method call, B->A lexical: the planted
        # inversion the acceptance list names
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def outer(self):\n"
            "        with self._a:\n"
            "            self.helper()\n"
            "    def helper(self):\n"
            "        with self._b:\n"
            "            pass\n"
            "    def other(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n"
        ))
        assert "GL601" in active_ids(res)

    def test_positive_across_classes(self, tmp_path):
        # Outer holds _ol and calls into Inner (takes _il); Inner holds
        # _il and calls back through its owner ref (takes _ol) — the
        # cross-class cycle resolved via `self.x = Class(...)` typing
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class Outer:\n"
            "    def __init__(self):\n"
            "        self._ol = threading.Lock()\n"
            "        self.inner = Inner(self)\n"
            "    def fwd(self):\n"
            "        with self._ol:\n"
            "            self.inner.work()\n"
            "    def notify(self):\n"
            "        with self._ol:\n"
            "            pass\n"
            "class Inner:\n"
            "    def __init__(self, owner):\n"
            "        self._il = threading.Lock()\n"
            "        self.owner = Outer()\n"
            "    def work(self):\n"
            "        with self._il:\n"
            "            pass\n"
            "    def back(self):\n"
            "        with self._il:\n"
            "            self.owner.notify()\n"
        ), filename="locks.py")
        assert "GL601" in active_ids(res)

    def test_negative_one_directional_cross_class(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class Inner:\n"
            "    def __init__(self):\n"
            "        self._il = threading.Lock()\n"
            "    def work(self):\n"
            "        with self._il:\n"
            "            pass\n"
            "class Outer:\n"
            "    def __init__(self):\n"
            "        self._ol = threading.Lock()\n"
            "        self.inner = Inner()\n"
            "    def fwd(self):\n"
            "        with self._ol:\n"
            "            self.inner.work()\n"
        ), filename="locks.py")
        assert "GL601" not in active_ids(res)

    def test_negative_consistent_order(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "    def two(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
        ))
        assert "GL601" not in active_ids(res)

    def test_positive_unrelated_deep_chain_does_not_mask(self, tmp_path):
        # regression: a deep unrelated call chain must not poison the
        # acquisition analysis for a direct, shallow inversion
        deep = "".join(
            f"    def h{i}(self):\n        self.h{i + 1}()\n"
            for i in range(1, 7)
        )
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def deep_first(self):\n"
            "        self.h1()\n"
        ) + deep + (
            "    def h7(self):\n"
            "        with self._b:\n"
            "            pass\n"
            "    def shallow(self):\n"
            "        with self._a:\n"
            "            self.h7()\n"
            "    def inverted(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n"
        ))
        assert "GL601" in active_ids(res)

    def test_negative_callback_defined_not_called(self, tmp_path):
        # a nested def ACQUIRING b runs later, outside the caller's
        # lock scope — defining it under `with self.a` is not a->b
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def m1(self):\n"
            "        with self._a:\n"
            "            return self.m2()\n"
            "    def m2(self):\n"
            "        def cb():\n"
            "            with self._b:\n"
            "                pass\n"
            "        return cb\n"
            "    def m3(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n"
        ))
        assert "GL601" not in active_ids(res)

    def test_negative_nested_same_lock_rlock_style(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._a = threading.RLock()\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            self.two()\n"
            "    def two(self):\n"
            "        with self._a:\n"
            "            pass\n"
        ))
        assert "GL601" not in active_ids(res)

    def test_suppressed(self, tmp_path):
        # edges are reported at the INNER acquisition's `with` line
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            with self._b:  "
            "# graftlint: disable=GL601 (fixture)\n"
            "                pass\n"
            "    def two(self):\n"
            "        with self._b:\n"
            "            with self._a:  "
            "# graftlint: disable=GL601 (fixture)\n"
            "                pass\n"
        ))
        assert "GL601" not in active_ids(res)
        assert "GL601" in all_ids(res)


class TestGL602BlockingUnderLock:
    def test_positive_sleep(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def a(self):\n"
            "        with self._lock:\n"
            "            time.sleep(1.0)\n"
        ))
        assert "GL602" in active_ids(res)

    def test_positive_queue_get_no_timeout(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._q = queue.Queue()\n"
            "    def a(self):\n"
            "        with self._lock:\n"
            "            return self._q.get()\n"
        ))
        assert "GL602" in active_ids(res)

    def test_positive_thread_join(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._t = threading.Thread(target=print)\n"
            "    def a(self):\n"
            "        with self._lock:\n"
            "            self._t.join()\n"
        ))
        assert "GL602" in active_ids(res)

    def test_positive_event_wait(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._evt = threading.Event()\n"
            "    def a(self):\n"
            "        with self._lock:\n"
            "            self._evt.wait()\n"
        ))
        assert "GL602" in active_ids(res)

    def test_negative_queue_get_with_timeout(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._q = queue.Queue()\n"
            "    def a(self):\n"
            "        with self._lock:\n"
            "            return self._q.get(timeout=0.5)\n"
        ))
        assert "GL602" not in active_ids(res)

    def test_negative_queue_get_nonblocking(self, tmp_path):
        # get(False) / get(block=False) return immediately — the
        # standard non-blocking idiom must not fail the gate
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._q = queue.Queue()\n"
            "    def a(self):\n"
            "        with self._lock:\n"
            "            return self._q.get(False)\n"
            "    def b(self):\n"
            "        with self._lock:\n"
            "            return self._q.get(block=False)\n"
        ))
        assert "GL602" not in active_ids(res)

    def test_negative_cond_wait_on_held_condition(self, tmp_path):
        # Condition.wait RELEASES the held condition — correct idiom
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._cond = threading.Condition()\n"
            "    def a(self):\n"
            "        with self._cond:\n"
            "            self._cond.wait()\n"
        ))
        assert "GL602" not in active_ids(res)

    def test_positive_cond_wait_still_holding_other(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._cond = threading.Condition()\n"
            "    def a(self):\n"
            "        with self._lock:\n"
            "            with self._cond:\n"
            "                self._cond.wait()\n"
        ))
        assert "GL602" in active_ids(res)

    def test_negative_sleep_outside_lock(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def a(self):\n"
            "        with self._lock:\n"
            "            x = 1\n"
            "        time.sleep(1.0)\n"
        ))
        assert "GL602" not in active_ids(res)

    def test_negative_str_join_is_not_blocking(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.names = ['a']\n"
            "    def a(self):\n"
            "        with self._lock:\n"
            "            return ', '.join(self.names)\n"
        ))
        assert "GL602" not in active_ids(res)

    def test_suppressed(self, tmp_path):
        res = lint_src(tmp_path, LOCKS_HEADER + (
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def a(self):\n"
            "        with self._lock:\n"
            "            time.sleep(1.0)  "
            "# graftlint: disable=GL602 (fixture)\n"
        ))
        assert "GL602" not in active_ids(res)
        assert "GL602" in all_ids(res)


class TestParseErrors:
    def test_unparseable_file_is_reported(self, tmp_path):
        res = lint_src(tmp_path, "def broken(:\n")
        assert res.parse_errors, "torn file must be surfaced, not skipped"


class TestCLI:
    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, str(GRAFTLINT), *argv],
            capture_output=True, text=True, cwd=str(REPO),
        )

    def test_json_output_is_stable_and_parseable(self, tmp_path):
        (tmp_path / "m.py").write_text(JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    return x.item()\n"
        ))
        r1 = self._run("--json", str(tmp_path))
        r2 = self._run("--json", str(tmp_path))
        assert r1.returncode == 1  # active finding -> gate fails
        assert r1.stdout == r2.stdout, "JSON output must be deterministic"
        doc = json.loads(r1.stdout)
        assert doc["graftlint"] == 1
        assert doc["summary"]["active"] == 1
        assert doc["rules"] == sorted(RULES_BY_ID)
        (f,) = [x for x in doc["findings"] if not x["suppressed"]]
        assert set(f) == {
            "path", "line", "rule", "name", "severity", "message",
            "hint", "suppressed",
        }
        assert f["rule"] == "GL101"
        assert f["severity"] == "error"
        assert f["line"] == 5

    def test_clean_tree_exits_zero(self, tmp_path):
        (tmp_path / "m.py").write_text("def ok():\n    return 1\n")
        r = self._run("--json", str(tmp_path))
        assert r.returncode == 0
        doc = json.loads(r.stdout)
        assert doc["summary"]["active"] == 0

    def test_findings_sorted(self, tmp_path):
        (tmp_path / "b.py").write_text(JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    print(x)\n"
            "    return x.item()\n"
        ))
        (tmp_path / "a.py").write_text(JIT_HEADER + (
            "@jax.jit\n"
            "def g(x):\n"
            "    return x.item()\n"
        ))
        doc = json.loads(self._run("--json", str(tmp_path)).stdout)
        keys = [(f["path"], f["line"], f["rule"]) for f in doc["findings"]]
        assert keys == sorted(keys)

    def test_list_rules(self):
        r = self._run("--list-rules")
        assert r.returncode == 0
        for rule in RULES:
            assert rule.id in r.stdout

    def test_no_paths_is_usage_error(self):
        assert self._run().returncode == 2

    def test_unknown_rule_is_usage_error(self, tmp_path):
        # a typoed --rules must not lint nothing and exit 0 (a
        # misconfigured CI gate would pass forever)
        (tmp_path / "m.py").write_text("x = 1\n")
        r = self._run("--rules", "GL999", str(tmp_path))
        assert r.returncode == 2
        assert "unknown rule" in r.stderr

    def test_nonexistent_path_is_usage_error(self, tmp_path):
        # same contract as unknown rules: a typoed/renamed path must
        # not scan zero files and exit 0
        r = self._run(str(tmp_path / "renamed_away"))
        assert r.returncode == 2
        assert "does not exist" in r.stderr

    def test_path_with_no_py_files_is_usage_error(self, tmp_path):
        (tmp_path / "README.txt").write_text("no python here\n")
        r = self._run(str(tmp_path))
        assert r.returncode == 2
        assert "no .py files" in r.stderr

    def test_non_py_file_arg_is_usage_error(self, tmp_path):
        target = tmp_path / "notes.txt"
        target.write_text("x = 1\n")
        r = self._run(str(target))
        assert r.returncode == 2
        assert "no .py files" in r.stderr

    def test_parse_error_fails_gate(self, tmp_path):
        (tmp_path / "broken.py").write_text("def broken(:\n")
        r = self._run("--json", str(tmp_path))
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        assert len(doc["parse_errors"]) == 1
        assert doc["parse_errors"][0].endswith("broken.py")

    def test_list_rules_shows_all_families(self):
        r = self._run("--list-rules")
        assert r.returncode == 0
        for fam in ("GL101", "GL201", "GL301", "GL401", "GL402", "GL403",
                    "GL501", "GL502", "GL503", "GL504", "GL601", "GL602"):
            assert fam in r.stdout, f"{fam} missing from --list-rules"
        assert "[warning]" in r.stdout  # GL503's severity is surfaced

    def test_warning_severity_does_not_gate(self, tmp_path):
        (tmp_path / "m.py").write_text(
            "import jax\nimport jax.numpy as jnp\n"
            "from jax.experimental import pallas as pl\n"
            "from jax.experimental.pallas import tpu as pltpu\n"
            "def kern(x_ref, o_ref, acc_ref):\n"
            "    o_ref[...] = x_ref[...]\n"
            "def call(x, M):\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        out_shape=jax.ShapeDtypeStruct((M, 128), jnp.float32),\n"
            "        scratch_shapes=[pltpu.VMEM((2048, 4096),\n"
            "                                   jnp.float32)],\n"
            "    )(x)\n"
        )
        r = self._run("--json", str(tmp_path))
        doc = json.loads(r.stdout)
        assert r.returncode == 0, "a lone GL503 warning must not gate"
        assert doc["summary"]["active"] == 1
        assert doc["summary"]["warnings"] == 1
        (f,) = doc["findings"]
        assert f["rule"] == "GL503" and f["severity"] == "warning"

    def test_vmem_budget_flag(self, tmp_path):
        (tmp_path / "m.py").write_text(
            "import jax\nimport jax.numpy as jnp\n"
            "from jax.experimental import pallas as pl\n"
            "from jax.experimental.pallas import tpu as pltpu\n"
            "def kern(x_ref, o_ref, acc_ref):\n"
            "    o_ref[...] = x_ref[...]\n"
            "def call(x, M):\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        out_shape=jax.ShapeDtypeStruct((M, 128), jnp.float32),\n"
            "        scratch_shapes=[pltpu.VMEM((2048, 4096),\n"
            "                                   jnp.float32)],\n"
            "    )(x)\n"
        )
        doc = json.loads(
            self._run("--json", "--vmem-budget", "64", str(tmp_path)).stdout
        )
        assert doc["summary"]["active"] == 0
        doc = json.loads(
            self._run("--json", "--vmem-budget", "8", str(tmp_path)).stdout
        )
        assert doc["summary"]["active"] == 1


class TestSarifOutput:
    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, str(GRAFTLINT), *argv],
            capture_output=True, text=True, cwd=str(REPO),
        )

    def _fixture(self, tmp_path):
        (tmp_path / "m.py").write_text(JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    print(x)\n"
            "    return x.item()\n"
            "@jax.jit\n"
            "def g(x):\n"
            "    return x.tolist()  # graftlint: disable=GL101 (fixture)\n"
        ))

    def test_schema_and_determinism(self, tmp_path):
        self._fixture(tmp_path)
        r1 = self._run("--format", "sarif", str(tmp_path))
        r2 = self._run("--format", "sarif", str(tmp_path))
        assert r1.returncode == 1  # active findings still gate
        assert r1.stdout == r2.stdout, "SARIF must be deterministic"
        doc = json.loads(r1.stdout)
        assert doc["version"] == "2.1.0"
        assert "sarif" in doc["$schema"]
        (run,) = doc["runs"]
        driver = run["tool"]["driver"]
        assert driver["name"] == "graftlint"
        rule_ids = [r["id"] for r in driver["rules"]]
        assert rule_ids == sorted(rule_ids)
        from differential_transformer_replication_tpu.analysis import (
            RULES_BY_ID as _R,
        )
        assert set(rule_ids) == set(_R)
        for res in run["results"]:
            assert set(res) >= {"ruleId", "level", "message", "locations"}
            (loc,) = res["locations"]
            phys = loc["physicalLocation"]
            assert phys["artifactLocation"]["uri"].endswith("m.py")
            assert phys["region"]["startLine"] >= 1

    def test_suppressed_findings_carried_as_suppressions(self, tmp_path):
        self._fixture(tmp_path)
        doc = json.loads(
            self._run("--format", "sarif", str(tmp_path)).stdout
        )
        sup = [
            r for r in doc["runs"][0]["results"] if r.get("suppressions")
        ]
        assert len(sup) == 1
        assert sup[0]["suppressions"] == [{"kind": "inSource"}]

    def test_warning_level_mapped(self, tmp_path):
        (tmp_path / "m.py").write_text(
            "import jax\nimport jax.numpy as jnp\n"
            "from jax.experimental import pallas as pl\n"
            "from jax.experimental.pallas import tpu as pltpu\n"
            "def kern(x_ref, o_ref, acc_ref):\n"
            "    o_ref[...] = x_ref[...]\n"
            "def call(x, M):\n"
            "    return pl.pallas_call(\n"
            "        kern,\n"
            "        out_shape=jax.ShapeDtypeStruct((M, 128), jnp.float32),\n"
            "        scratch_shapes=[pltpu.VMEM((2048, 4096),\n"
            "                                   jnp.float32)],\n"
            "    )(x)\n"
        )
        r = self._run("--format", "sarif", str(tmp_path))
        assert r.returncode == 0
        doc = json.loads(r.stdout)
        (res,) = doc["runs"][0]["results"]
        assert res["ruleId"] == "GL503" and res["level"] == "warning"

    def test_json_conflict_is_usage_error(self, tmp_path):
        (tmp_path / "m.py").write_text("x = 1\n")
        r = self._run("--json", "--format", "sarif", str(tmp_path))
        assert r.returncode == 2


class TestChangedMode:
    def _run(self, *argv, cwd):
        return subprocess.run(
            [sys.executable, str(GRAFTLINT), *argv],
            capture_output=True, text=True, cwd=cwd,
        )

    def _git(self, cwd, *argv):
        return subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", *argv],
            cwd=str(cwd), capture_output=True, text=True, check=True,
        )

    def _repo(self, tmp_path):
        self._git(tmp_path, "init", "-q")
        (tmp_path / "old_bad.py").write_text(JIT_HEADER + (
            "@jax.jit\n"
            "def f(x):\n"
            "    return x.item()\n"
        ))
        self._git(tmp_path, "add", "old_bad.py")
        self._git(tmp_path, "commit", "-qm", "seed")
        return tmp_path

    def test_only_changed_files_reported(self, tmp_path):
        repo = self._repo(tmp_path)
        # a NEW untracked hazard file and an UNCHANGED committed one
        (repo / "new_bad.py").write_text(JIT_HEADER + (
            "@jax.jit\n"
            "def g(x):\n"
            "    print(x)\n"
            "    return x\n"
        ))
        r = self._run("--changed", "HEAD", "--json", ".", cwd=str(repo))
        doc = json.loads(r.stdout)
        assert r.returncode == 1
        assert doc["changed_vs"] == "HEAD"
        paths = {f["path"] for f in doc["findings"]}
        assert all(p.endswith("new_bad.py") for p in paths), paths
        # ...while the full run still sees both
        r_full = self._run("--json", ".", cwd=str(repo))
        full_paths = {
            f["path"] for f in json.loads(r_full.stdout)["findings"]
        }
        assert any(p.endswith("old_bad.py") for p in full_paths)

    def test_unchanged_tree_exits_zero(self, tmp_path):
        repo = self._repo(tmp_path)
        r = self._run("--changed", "HEAD", ".", cwd=str(repo))
        assert r.returncode == 0, r.stdout + r.stderr

    def test_call_graph_spans_whole_tree(self, tmp_path):
        # the hazard lives in an UNTOUCHED helper module; the CHANGED
        # file jits a function that calls it. Cross-module reachability
        # must survive the file filter: the finding lands in the helper
        # (unchanged -> filtered out, exit 0), but the jit-region count
        # proves the whole tree was analyzed, and editing the helper
        # itself surfaces it.
        repo = self._repo(tmp_path)
        (repo / "helper.py").write_text(
            "def deep(x):\n"
            "    return x.item()\n"
        )
        self._git(repo, "add", "helper.py")
        self._git(repo, "commit", "-qm", "helper")
        (repo / "entry.py").write_text(JIT_HEADER + (
            "from helper import deep\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return deep(x)\n"
        ))
        r = self._run("--changed", "HEAD", "--json", ".", cwd=str(repo))
        doc = json.loads(r.stdout)
        # finding is attributed to helper.py (unchanged) -> filtered;
        # nothing in entry.py itself
        assert all(
            not f["path"].endswith("entry.py") for f in doc["findings"]
        )
        # whole-tree analysis really happened (files_scanned is global)
        assert doc["files_scanned"] == 3
        # now touch the helper too: the finding surfaces in changed mode
        (repo / "helper.py").write_text(
            "def deep(x):\n"
            "    return x.item()\n"
            "\n"
            "def deep2(x):\n"
            "    return x\n"
        )
        r2 = self._run("--changed", "HEAD", "--json", ".", cwd=str(repo))
        doc2 = json.loads(r2.stdout)
        assert any(
            f["path"].endswith("helper.py") and f["rule"] == "GL101"
            for f in doc2["findings"]
        )
        assert r2.returncode == 1

    def test_findings_survive_symlinked_path(self, tmp_path):
        # git reports the PHYSICAL toplevel; reaching the repo through
        # a symlink must not silently filter every finding (gate would
        # pass on real hazards)
        (tmp_path / "real").mkdir()
        repo = self._repo(tmp_path / "real")
        (repo / "new_bad.py").write_text(JIT_HEADER + (
            "@jax.jit\n"
            "def g(x):\n"
            "    return x.item()\n"
        ))
        link = tmp_path / "link"
        link.symlink_to(repo)
        r = self._run("--changed", "HEAD", "--json", ".", cwd=str(link))
        doc = json.loads(r.stdout)
        assert r.returncode == 1
        assert any(
            f["path"].endswith("new_bad.py") for f in doc["findings"]
        )

    def test_bad_ref_is_usage_error(self, tmp_path):
        repo = self._repo(tmp_path)
        r = self._run("--changed", "no-such-ref", ".", cwd=str(repo))
        assert r.returncode == 2
        assert "git diff" in r.stderr

    def test_outside_git_is_usage_error(self, tmp_path):
        (tmp_path / "m.py").write_text("x = 1\n")
        import os as _os
        env_dir = tmp_path / "isolated"
        env_dir.mkdir()
        (env_dir / "m.py").write_text("x = 1\n")
        r = subprocess.run(
            [sys.executable, str(GRAFTLINT), "--changed", "HEAD", "m.py"],
            capture_output=True, text=True, cwd=str(env_dir),
            env={**_os.environ, "GIT_CEILING_DIRECTORIES": str(tmp_path)},
        )
        assert r.returncode == 2


class TestGL301CoversPagePool:
    """Mutation test for the paged-KV pool's lock discipline
    (serving/pages.py): PagePool is a lock-owning class shared between
    the engine thread and /health readers, so GL301 is the machine
    check that its refcount/accounting writes stay under
    ``self._lock``. Planting exactly that bug — an admission-side
    counter write hoisted OUT of the lock — in the real module source
    MUST fire; the unmutated module must stay clean."""

    PAGES = (
        REPO / "differential_transformer_replication_tpu" / "serving"
        / "pages.py"
    )
    ANCHOR = (
        "        with self._lock:\n"
        "            self._clock += 1\n"
        "            for n in self._slot_nodes[slot]:"
    )

    def _copy(self, tmp_path, src):
        # keep the serving/ path component: GL301 is a serving-dir rule
        path = tmp_path / "serving" / "pages.py"
        path.parent.mkdir(parents=True)
        path.write_text(src)
        return path

    def test_unmutated_pages_is_lock_clean(self, tmp_path):
        path = self._copy(tmp_path, self.PAGES.read_text())
        result = lint_paths([str(path)],
                            rules=["GL301", "GL601", "GL602"])
        assert active_ids(result) == []

    def test_planted_off_lock_refcount_write_fires(self, tmp_path):
        src = self.PAGES.read_text()
        assert self.ANCHOR in src, (
            "mutation anchor vanished — PagePool.release's lock block "
            "moved; update the anchor so this mutation test keeps "
            "guarding it"
        )
        mutated = src.replace(
            self.ANCHOR,
            "        self._hits += 1  # planted: off-lock write\n"
            + self.ANCHOR,
        )
        path = self._copy(tmp_path, mutated)
        result = lint_paths([str(path)], rules=["GL301"])
        assert active_ids(result) == ["GL301"]
        (finding,) = result.active
        assert "_hits" in finding.message

    def test_planted_write_under_lock_stays_clean(self, tmp_path):
        # negative control: the same write INSIDE the lock block is the
        # correct idiom and must not fire
        src = self.PAGES.read_text()
        mutated = src.replace(
            self.ANCHOR,
            "        with self._lock:\n"
            "            self._hits += 0  # inside the lock: fine\n"
            "            self._clock += 1\n"
            "            for n in self._slot_nodes[slot]:",
        )
        path = self._copy(tmp_path, mutated)
        result = lint_paths([str(path)], rules=["GL301"])
        assert active_ids(result) == []


class TestGL602CoversResilienceThreads:
    """Mutation test for the heartbeat/watchdog threads' lock usage:
    GL602 is the machine check that those daemon threads never block
    under a held lock (a heartbeat monitor sleeping under its lock
    would stall the publisher — and with it the liveness signal every
    peer depends on). Planting exactly that bug in the real module
    source MUST fire; the unmutated module must stay clean."""

    HEARTBEAT = (
        REPO / "differential_transformer_replication_tpu" / "parallel"
        / "heartbeat.py"
    )
    ANCHOR = (
        "with self._lock:\n"
        "            for p in list(self._last_change):"
    )

    def test_unmutated_heartbeat_is_gl602_clean(self, tmp_path):
        src = self.HEARTBEAT.read_text()
        (tmp_path / "heartbeat.py").write_text(src)
        result = lint_paths([str(tmp_path / "heartbeat.py")],
                            rules=["GL601", "GL602"])
        assert active_ids(result) == []

    def test_planted_blocking_call_under_lock_fires(self, tmp_path):
        src = self.HEARTBEAT.read_text()
        assert self.ANCHOR in src, (
            "mutation anchor vanished — heartbeat.py's monitor lock "
            "block moved; update the anchor so this mutation test "
            "keeps guarding it"
        )
        mutated = src.replace(
            self.ANCHOR,
            "with self._lock:\n"
            "            time.sleep(0.5)  # planted: blocking under lock\n"
            "            for p in list(self._last_change):",
        )
        (tmp_path / "heartbeat.py").write_text(mutated)
        result = lint_paths([str(tmp_path / "heartbeat.py")],
                            rules=["GL602"])
        assert active_ids(result) == ["GL602"]
        (finding,) = result.active
        assert "time.sleep" in finding.message
        assert "Heartbeat._lock" in finding.message

    def test_planted_lockless_sleep_stays_clean(self, tmp_path):
        """The negative control: the same sleep OUTSIDE the lock is the
        correct pacing idiom and must not fire (otherwise the clean
        gate would force suppressions onto legitimate code)."""
        src = self.HEARTBEAT.read_text()
        mutated = src.replace(
            self.ANCHOR,
            "time.sleep(0.0)  # outside the lock: fine\n"
            "        " + self.ANCHOR,
        )
        (tmp_path / "heartbeat.py").write_text(mutated)
        result = lint_paths([str(tmp_path / "heartbeat.py")],
                            rules=["GL602"])
        assert active_ids(result) == []
