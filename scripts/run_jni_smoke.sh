#!/bin/bash
# End-to-end JNI smoke test: a REAL JVM loads the L4 shim
# (libspark_rapids_tpu_jni.so), which embeds CPython and routes ops into
# the spark_rapids_tpu runtime.  Mirrors the reference call stack
# (SURVEY.md §3.1): Java Hash.murmurHash32 -> JNI -> native -> device.
#
# Exits 0 on pass, 2 when no JVM is available (skip), 1 on failure.
set -e
cd "$(dirname "$0")/.."
REPO="$(pwd)"

# -- find a JVM: system java, or bazel's embedded JRE ------------------
JAVA_BIN="${SPARK_RAPIDS_JAVA:-}"
if [ -z "$JAVA_BIN" ] && command -v java >/dev/null 2>&1; then
    JAVA_BIN=java
fi
if [ -z "$JAVA_BIN" ]; then
    for d in "$HOME"/.cache/bazel/_bazel_*/install/*/embedded_tools/jdk/bin/java; do
        [ -x "$d" ] && JAVA_BIN="$d" && break
    done
fi
if [ -z "$JAVA_BIN" ] && command -v bazel >/dev/null 2>&1; then
    (cd /tmp && bazel version >/dev/null 2>&1) || true
    for d in "$HOME"/.cache/bazel/_bazel_*/install/*/embedded_tools/jdk/bin/java; do
        [ -x "$d" ] && JAVA_BIN="$d" && break
    done
fi
if [ -z "$JAVA_BIN" ]; then
    echo "jni-smoke: SKIP (no JVM available)" >&2
    exit 2
fi

# -- build shim + classes ---------------------------------------------
bash native/jni/build.sh
python scripts/gen_java_classes.py java/classes

# -- run ---------------------------------------------------------------
# Pin the CPU backend: the smoke proves the JVM->JNI->CPython->XLA
# path, not chip perf.  jni_entry.initialize pins via jax.config as
# well, in case the embedded interpreter imported jax earlier.
export JAX_PLATFORMS=cpu
export SPARK_RAPIDS_TPU_PLATFORM=cpu
export SPARK_RAPIDS_TPU_ROOT="$REPO"
# 4 virtual CPU devices: the smoke drives a multi-device SPMD query
# (shard_map q5) from the JVM
export SPARK_RAPIDS_TPU_CPU_DEVICES=4
"$JAVA_BIN" -cp "$REPO/java/classes" \
    com.nvidia.spark.rapids.jni.JniSmokeTest \
    "$REPO/native/jni/libspark_rapids_tpu_jni.so"
# typed OOM exceptions across JNI (GpuRetryOOM / GpuSplitAndRetryOOM
# caught by real JVM catch blocks; class file major 49 for try/catch
# without StackMapTable)
"$JAVA_BIN" -cp "$REPO/java/classes" \
    com.nvidia.spark.rapids.jni.OomSmokeTest \
    "$REPO/native/jni/libspark_rapids_tpu_jni.so"
# the BUFN deadlock-break cycle with two REAL concurrent JVM threads
# (RmmSparkTest.testBasicBUFN analog through the JNI surface)
exec timeout 300 "$JAVA_BIN" -cp "$REPO/java/classes" \
    com.nvidia.spark.rapids.jni.BufnSmokeTest \
    "$REPO/native/jni/libspark_rapids_tpu_jni.so"
