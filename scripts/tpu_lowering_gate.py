"""CI gate: prove the device engines still LOWER for platform 'tpu'.

The u64-dense scan kernels (Ryu float->string, Eisel-Lemire
string->float, SHA-2, xxhash64/murmur3, the JSON pushdown scan, the
kudo blob gathers, decimal128 limb math) run in tests only on the CPU
backend (tests/conftest.py pins it) — so nothing would notice if one
of these engines stopped *lowering* for TPU.  This gate closes that
hole without needing the chip at all: `jax.export` cross-lowers each
jitted core to StableHLO with platforms=['tpu'], which runs every
TPU-specific lowering rule deviceless.

Run:  python scripts/tpu_lowering_gate.py     (exit 1 on any failure)
Wired into `make ci`.

Reference analog: the premerge GPU build proving every .cu still
compiles (ci/Jenkinsfile.premerge:196-232).  Caveat: this gate runs
JAX's TPU *lowering rules* to StableHLO; the XLA:TPU backend compile
(tiling/layout legality) still needs the real chip, so a green gate
proves lowering, not end-to-end compilation or numerics.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np
import jax.numpy as jnp
from jax import export

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_tpu.columns import dtypes
from spark_rapids_tpu.columns.column import Column
from spark_rapids_tpu.columns.table import Table


def _specs():
    """(name, jitted_fn, args) for every device engine's compiled core."""
    rng = np.random.default_rng(3)
    chars = jnp.asarray(rng.integers(32, 127, (8, 24)), jnp.uint8)
    lens = jnp.full((8,), 24, jnp.int32)
    start = jnp.zeros((8,), jnp.int32)
    end = lens
    bits64 = jnp.asarray(rng.integers(0, 1 << 63, 8, np.uint64), jnp.uint64)
    bits32 = jnp.asarray(rng.integers(0, 1 << 31, 8, np.uint32), jnp.uint32)
    limbs = jnp.asarray(rng.integers(0, 1 << 31, (8, 4), np.int64)
                        .astype(np.uint32))

    from spark_rapids_tpu.ops import ftos_device, stod_device, sha_device
    from spark_rapids_tpu.ops import hash as hash_ops
    from spark_rapids_tpu.ops import json_device, decimal_device
    from spark_rapids_tpu.ops import row_conversion as rc
    from spark_rapids_tpu.shuffle import device_split

    int_col = Column.from_numpy(np.arange(8, dtype=np.int64),
                                dtype=dtypes.INT64)
    f32_col = Column.from_numpy(np.linspace(0, 1, 8, dtype=np.float32),
                                dtype=dtypes.FLOAT32)
    fixed_table = Table([int_col, f32_col])

    from spark_rapids_tpu.ops.json_path import parse_path
    json_scan = json_device._build_scan(
        json_device._compile_path(parse_path("$.a.b")))
    jchars = jnp.concatenate(
        [chars, jnp.zeros((8, 1), jnp.uint8)], axis=1)

    pool = jnp.zeros(256, jnp.uint8)
    dst = jnp.asarray([0, 64], jnp.int64)
    src = jnp.asarray([0, 128], jnp.int64)

    from spark_rapids_tpu.ops import (parse_uri_device, protobuf_device,
                                      raw_map_device)
    # (fnum, wire, strict, repeated, cap): varint / len / f64 / f32 +
    # a repeated varint field so the packed-mode state machine lowers
    pb_specs = ((1, 0, False, False, 8), (2, 2, False, False, 8),
                (3, 1, False, False, 8), (4, 5, False, False, 8),
                (5, 0, False, True, 8))

    return [
        ("ftos_d2d", ftos_device._d2d, (bits64,)),
        ("ftos_f2d", ftos_device._f2d, (bits32,)),
        ("stod_parse_scan", stod_device._parse_scan, (chars, start, end)),
        ("stod_strip_bounds", stod_device._strip_bounds, (chars, lens)),
        ("stod_narrow_f32", stod_device._narrow_to_f32, (bits64,)),
        ("sha256", lambda c, l: sha_device._sha_jit(c, l, 256),
         (chars, lens)),
        ("sha512", lambda c, l: sha_device._sha_jit(c, l, 512),
         (chars, lens)),
        ("murmur3_32", lambda t: hash_ops.murmur3_32(t, seed=42),
         (fixed_table,)),
        ("xxhash64", lambda t: hash_ops.xxhash64(t), (fixed_table,)),
        ("json_scan", json_scan, (jchars, lens)),
        ("kudo_gather_sections",
         lambda p, d, s: device_split._gather_sections_kernel(
             p, d, s, jnp.int64(128), 128), (pool, dst, src)),
        ("kudo_gather_i32",
         lambda b, p: device_split._gather_i32_kernel(b, p, 8),
         (pool, jnp.arange(8, dtype=jnp.int64))),
        ("decimal_multiply",
         lambda a, b: decimal_device._multiply_core(a, b, 2, 2, 4),
         (limbs, limbs)),
        ("decimal_add",
         lambda a, b: decimal_device._add_sub_core(a, b, 2, 2, 2, False),
         (limbs, limbs)),
        ("row_conversion_to_rows",
         lambda t: rc.convert_to_rows(t), (fixed_table,)),
        ("protobuf_decode",
         lambda ch, ln: protobuf_device._decode_chunk(ch, ln, pb_specs),
         (chars, lens)),
        ("parse_uri_analyze", parse_uri_device._analyze,
         (chars, lens)),
        ("raw_map_scan", raw_map_device._scan_raw_map, (chars, lens)),
    ]


def main():
    failures = []
    specs = _specs()
    for name, fn, args in specs:
        try:
            exp = export.export(jax.jit(fn), platforms=["tpu"])(*args)
            nbytes = len(exp.mlir_module())
            print(f"  lower[tpu] ok   {name:24s} ({nbytes} B stablehlo)")
        except Exception as e:  # noqa: BLE001 — report every engine
            failures.append((name, e))
            msg = str(e).splitlines()[0][:200]
            print(f"  lower[tpu] FAIL {name:24s} {type(e).__name__}: {msg}")
    if failures:
        print(f"tpu_lowering_gate: {len(failures)} engine(s) no longer "
              "lower for TPU", file=sys.stderr)
        return 1
    print(f"tpu_lowering_gate: all {len(specs)} engines lower for TPU")
    return 0


if __name__ == "__main__":
    sys.exit(main())
