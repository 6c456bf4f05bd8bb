"""The program's ``jax.named_scope`` s reach the compiled HLO's ``op_name``.

A CP 2 train step of the smoke-sized starcoder2_3b (flashcp, Pallas in
interpret mode, chunked exchange) is compiled on two simulated CPU
devices in a child process, so that they never leak into this process's
JAX runtime.  Each scope must name some operation of the step.
"""

import json
import os
import re
import subprocess
import sys

import pytest

SCOPES = ("attention", "mlp", "head_loss", "optimizer", "cp_exchange")

CHILD = r"""
import json, os, re, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, sys.argv[1])
import jax
from repro.configs import RunConfig, get_config, reduce_for_smoke
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import build_train_step

cfg = reduce_for_smoke(get_config("starcoder2_3b"))
run = RunConfig(arch="starcoder2_3b", cp_strategy="flashcp",
                attention_impl="pallas", cp_overlap="chunked",
                kernel_grid="flat")
b = build_train_step(cfg, make_local_mesh(1, 2), run,
                     ShapeConfig("t", 512, 1, "train"), q_chunk=128)
hlo = jax.jit(b.fn, in_shardings=b.in_shardings,
              out_shardings=b.out_shardings).lower(
                  *b.abstract_inputs).compile().as_text()
print(json.dumps(sorted(set(re.findall(r'op_name="([^"]*)"', hlo)))))
"""


@pytest.fixture(scope="module")
def op_names():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", CHILD, src],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_reaches_op_name(op_names, scope):
    # a scope is one component of the name stack: "a/<scope>/b", or
    # wrapped by a transformation: "jvp(<scope>)"
    pat = re.compile(rf"(^|[/(]){scope}([/)]|$)")
    assert any(pat.search(n) for n in op_names), \
        f"no op_name holds the scope {scope!r}"
