#!/usr/bin/env python
"""Run the survey-geometry sharded==single equality pass on the
builder's own clock (several minutes, a few GB on virtual CPU
devices).  Round 3 ran this inline in the driver's dryrun_multichip
gate and blew its timeout (MULTICHIP_r03.json rc=124); it now lives
here, out of the gate's budget.

Usage:
    python tools/survey_check.py [n_devices]

Always runs on virtual CPU devices (any inherited JAX_PLATFORMS is
overridden);
set TPULSAR_SURVEY_ON_DEVICE=1 to run on the real accelerator
instead.
"""

import os
import sys

n = int(sys.argv[1]) if len(sys.argv) > 1 else 8

# This is by definition a virtual-device CPU validation run
# (honouring an inherited accelerator platform would point an
# 8-device mesh at the one real chip).  TPULSAR_SURVEY_ON_DEVICE=1 is the explicit escape
# hatch.
if os.environ.get("TPULSAR_SURVEY_ON_DEVICE", "") != "1":
    inherited = os.environ.get("JAX_PLATFORMS", "").strip()
    if inherited and inherited != "cpu":
        print(f"[survey_check] overriding JAX_PLATFORMS={inherited} "
              "-> cpu (set TPULSAR_SURVEY_ON_DEVICE=1 for a real "
              "on-device run)", file=sys.stderr)
    os.environ["JAX_PLATFORMS"] = "cpu"
# REWRITE any inherited device-count flag rather than keeping it
# (round-4 advisor: a substring check that keeps an inherited
# --xla_force_host_platform_device_count=1 collapses the mesh to one
# device and the 'sharded==single equality' compares a run against
# itself)
import re

flags = os.environ.get("XLA_FLAGS", "")
flag = f"--xla_force_host_platform_device_count={n}"
if "xla_force_host_platform_device_count" in flags:
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                   flag, flags)
else:
    flags = f"{flags} {flag}".strip()
os.environ["XLA_FLAGS"] = flags

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import importlib

graft = importlib.import_module("__graft_entry__")

if __name__ == "__main__":
    graft.survey_geometry_check(n)
