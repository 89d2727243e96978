"""Emit the runnable JVM class files for the JNI binding smoke test.

The canonical API definition is the .java sources under java/src/ (same
package as the reference, com.nvidia.spark.rapids.jni, so code written
against the reference keeps its imports).  This image has a JRE (bazel's
embedded Zulu 21) but no Java compiler, so the classes actually executed
here are emitted with scripts/jasm.py from the declarative specs below.
The emitted surface is the subset the smoke test drives; the .java
sources carry the full documented API.

Golden values: murmur3 expectations are Spark-derived constants (same
vectors as tests/test_hash.py); xxhash64/cast goldens are computed by
the Python engines at emission time (those engines are themselves
golden-validated against Spark vectors in tests/).

Usage: python scripts/gen_java_classes.py [outdir]   (default java/classes)
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# pin the CPU backend BEFORE any spark_rapids_tpu import: the ops
# package builds device tables at import time, and generating class
# files must not take the chip
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from jasm import (ACC_FINAL, ACC_PRIVATE, ACC_PUBLIC, ACC_VOLATILE,
                  ClassFile, Code, Label, T_INT, T_LONG)  # noqa: E402

PKG = "com/nvidia/spark/rapids/jni"

# OOM taxonomy (reference: typed unchecked exceptions looked up by
# name from native, SparkResourceAdaptorJni.cpp:49-54).  Derived from
# the runtime's exception module so the Java classes can't drift from
# the Python names the shim maps by (bases excluded — only concrete
# thrown types cross JNI).
def _exception_classes():
    """{name: java_superclass} derived from the Python hierarchy, so a
    Java catch of a base type keeps matching subclasses exactly as the
    runtime's raises do."""
    import inspect

    from spark_rapids_tpu.memory import exceptions as mem_exc
    from spark_rapids_tpu.ops import exceptions as ops_exc
    names = set()
    bases = {}
    for mod in (mem_exc, ops_exc):
        for name, obj in vars(mod).items():
            if (inspect.isclass(obj) and issubclass(obj, Exception)
                    and not name.endswith("Base")):
                names.add(name)
                bases[name] = obj.__bases__[0].__name__
    return {n: (f"{PKG}/{bases[n]}" if bases[n] in names
                else "java/lang/RuntimeException")
            for n in sorted(names)}


EXCEPTION_CLASSES = _exception_classes()

# (class, [(method, descriptor)...]) — all public static native
NATIVE_CLASSES = {
    "TpuRuntime": [
        ("initialize", "()V"),
        ("shutdown", "()V"),
        ("liveHandles", "()I"),
        ("runDistributedQ5", "(III)[J"),
        ("runDistributedQ72", "(III)[J"),
    ],
    "TpuColumns": [
        ("fromLongs", "([J)J"),
        ("fromInts", "([I)J"),
        ("fromDoubles", "([D)J"),
        ("fromStrings", "([Ljava/lang/String;)J"),
        ("fromStringsBulk", "([B[I[B)J"),
        ("getStringChars", "(J)[B"),
        ("getStringOffsets", "(J)[B"),
        ("fromDecimals", "([JILjava/lang/String;)J"),
        ("getChild", "(JI)J"),
        ("gather", "(JJ)J"),
        ("free", "(J)V"),
    ],
    "DecimalUtils": [
        ("multiply128", "(JJI)[J"),
        ("divide128", "(JJI)[J"),
        ("add128", "(JJI)[J"),
        ("subtract128", "(JJI)[J"),
    ],
    "DeviceAttr": [
        ("isIntegratedGPU", "()Z"),
    ],
    "Protobuf": [
        ("decodeToStruct", "(J[I[Ljava/lang/String;[I[Z)J"),
    ],
    "IcebergBucket": [
        ("bucket", "(JI)J"),
    ],
    "IcebergTruncate": [
        ("truncate", "(JI)J"),
    ],
    "IcebergDateTimeUtil": [
        ("transform", "(JLjava/lang/String;)J"),
    ],
    "HyperLogLogPlusPlusHostUDF": [
        ("reduce", "(JI)J"),
        ("estimate", "(JI)J"),
    ],
    "Hash": [
        ("murmurHash32", "(I[J)J"),
        ("xxHash64", "(J[J)J"),
        ("hiveHash", "([J)J"),
    ],
    "RowConversion": [
        ("convertToRows", "([J)J"),
        ("convertFromRows", "(J[Ljava/lang/String;[I)[J"),
    ],
    "CastStrings": [
        ("toInteger", "(JZZLjava/lang/String;)J"),
        ("toFloat", "(JZLjava/lang/String;)J"),
        ("fromFloat", "(J)J"),
        ("toDate", "(JZ)J"),
        ("fromLongToBinary", "(J)J"),
        ("formatNumber", "(JI)J"),
    ],
    "JSONUtils": [
        ("getJsonObject", "(JLjava/lang/String;)J"),
        ("getJsonObjectMultiplePaths",
         "(J[Ljava/lang/String;JI)[J"),
    ],
    "Arithmetic": [
        ("multiply", "(JJZZ)J"),
        ("round", "(JILjava/lang/String;)J"),
    ],
    "Histogram": [
        ("createHistogramIfValid", "(JJ)J"),
        ("percentileFromHistogram", "(J[D)J"),
    ],
    "Map": [
        ("sortMapColumn", "(JZ)J"),
    ],
    "Profiler": [
        ("nativeInit", "(Ljava/lang/String;IZ)V"),
        ("nativeStart", "()V"),
        ("nativeStop", "()V"),
        ("nativeShutdown", "()V"),
    ],
    "RmmSpark": [
        ("setEventHandler", "(J)V"),
        ("clearEventHandler", "()V"),
        ("startDedicatedTaskThread", "(JJ)V"),
        ("currentThreadIsDedicatedToTask", "(J)V"),
        ("getCurrentThreadId", "()J"),
        ("taskDone", "(J)V"),
        ("forceRetryOOM", "(JI)V"),
        ("forceSplitAndRetryOOM", "(JI)V"),
        ("blockThreadUntilReady", "()V"),
        ("alloc", "(J)V"),
        ("dealloc", "(J)V"),
        ("getStateOf", "(J)Ljava/lang/String;"),
        ("shuffleThreadWorkingOnTasks", "([J)V"),
        ("poolThreadFinishedForTasks", "([J)V"),
    ],
    "StringUtils": [
        ("randomUUIDs", "(IJ)J"),
    ],
    "ParseURI": [
        ("parseProtocol", "(JZ)J"),
        ("parseHost", "(JZ)J"),
        ("parseQuery", "(JZ)J"),
        ("parsePath", "(JZ)J"),
        ("parseQueryWithKey", "(JLjava/lang/String;Z)J"),
    ],
    "GpuSubstringIndexUtils": [
        ("substringIndex", "(JLjava/lang/String;I)J"),
    ],
    "CharsetDecode": [
        ("decodeToUTF8", "(JLjava/lang/String;Ljava/lang/String;)J"),
    ],
    "ZOrder": [
        ("interleaveBits", "([J)J"),
        ("hilbertIndex", "(I[J)J"),
    ],
    "CaseWhen": [
        ("selectFirstTrueIndex", "([J)J"),
    ],
    "NumberConverter": [
        ("convertCvCv", "(JII)J"),
    ],
    "DateTimeUtils": [
        ("truncate", "(JLjava/lang/String;)J"),
    ],
    "DateTimeRebase": [
        ("rebaseGregorianToJulian", "(J)J"),
        ("rebaseJulianToGregorian", "(J)J"),
    ],
    "KudoSerializer": [
        ("writeToStream", "([JII)[B"),
        ("mergeToTable", "([B[Ljava/lang/String;[I)[J"),
        ("hostTableFromColumns", "([J)J"),
        ("writeHostTable", "(JII)[B"),
        ("mergeToHostTable", "([BJ)J"),
        ("hostTableNumRows", "(J)J"),
        ("freeHostTable", "(J)V"),
        ("hostTableToColumns", "(J)[J"),
    ],
    "HostTable": [
        ("fromTable", "([J)J"),
        ("sizeBytes", "(J)J"),
        ("toDeviceColumns", "(J)[J"),
        ("free", "(J)V"),
    ],
    "GpuListSliceUtils": [
        ("listSlice", "(JIIZ)J"),
        ("listSliceSC", "(JIJZ)J"),
        ("listSliceCS", "(JJIZ)J"),
        ("listSliceCC", "(JJJZ)J"),
    ],
    "MapUtils": [
        ("isValidMap", "(JZ)Z"),
        ("mapFromEntries", "(JZ)J"),
    ],
    "GpuMapZipWithUtils": [
        ("mapZip", "(JJ)J"),
    ],
    "OrcDstRuleExtractor": [
        ("timezoneInfoPacked", "(Ljava/lang/String;)[J"),
        ("timezoneIds", "()[Ljava/lang/String;"),
    ],
    "nvml/NVML": [
        ("getDeviceCount", "()I"),
        ("getSnapshotPacked", "(I)[J"),
        ("getDeviceName", "(I)Ljava/lang/String;"),
    ],
    "JoinPrimitives": [
        ("sortMergeInnerJoin", "([J[JZ)[J"),
    ],
    "BloomFilter": [
        ("create", "(III)J"),
        ("put", "(JJ)J"),
        ("probe", "(JJ)J"),
        ("merge", "([J)J"),
        ("serialize", "(J)[B"),
        ("deserialize", "([B)J"),
    ],
    "Aggregation64Utils": [
        ("extractChunk32From64bit", "(JLjava/lang/String;I)J"),
        ("assemble64FromSum", "(JJLjava/lang/String;)[J"),
    ],
    "RegexRewriteUtils": [
        ("literalRangePattern", "(JLjava/lang/String;III)J"),
    ],
    "GpuTimeZoneDB": [
        ("convertTimestampToUTC", "(JLjava/lang/String;)J"),
        ("convertUTCTimestampToTimeZone", "(JLjava/lang/String;)J"),
    ],
    "ParquetFooter": [
        ("readAndFilter", "([B[Ljava/lang/String;Z)[B"),
    ],
    "Version": [
        ("isVanilla320", "(IIII)Z"),
    ],
    "ThreadStateRegistry": [
        ("addThread", "(J)V"),
        ("removeThread", "(J)V"),
        ("knownThreads", "()[J"),
    ],
    "TaskPriority": [
        ("getTaskPriority", "(J)J"),
        ("taskDone", "(J)V"),
    ],
    "TestSupport": [
        ("assertTrue", "(ILjava/lang/String;)V"),
        ("checkLongColumn", "(J[J)I"),
        ("checkIntColumn", "(J[I)I"),
        ("checkStringColumn", "(J[Ljava/lang/String;)I"),
        ("checkColumnsEqual", "(JJ)I"),
        ("makeListOfInts", "([I[J)J"),
        ("makeMapColumn",
         "([I[Ljava/lang/String;[Ljava/lang/String;)J"),
    ],
}

# Spark-derived murmur3 goldens (tests/test_hash.py:27 vectors, the
# ASCII/non-null subset usable through JNI String[] marshalling)
MURMUR_IN = ["a", "B\nc",
             ("A very long (greater than 128 bytes/char string) to test "
              "a multi hash-step data point in the MD5 hash function. "
              "This string needed to be longer.A 60 character string to "
              "test MD5's message padding algorithm")]
MURMUR_GOLD = [1485273170, 1709559900, 176121990]


def _computed_goldens():
    """xxhash64 goldens from the (golden-validated) Python engine
    (CPU backend pinned once at module top)."""
    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.ops import xxhash64
    c = Column.from_pylist([1, 2, 3], dtypes.INT64)
    return xxhash64([c], 42).to_pylist()



def _emit_bulk_string_arrays(c, ch_slot, off_slot, i_slot, fill_byte,
                             nbytes=10_000_000, rows=500_000,
                             row_width=20):
    """Emit the 10MB chars fill + int32 offsets (i*row_width) loops
    shared by the smoke test and KudoBench bulk sections."""
    c.iconst(nbytes)
    c.newarray(8)
    c.astore(ch_slot)
    c.aload(ch_slot)
    c.iconst(fill_byte)
    c.invokestatic("java/util/Arrays", "fill", "([BB)V")
    oloop, odone = Label(), Label()
    c.iconst(rows + 1)
    c.newarray(T_INT)
    c.astore(off_slot)
    c.iconst(0)
    c.istore(i_slot)
    c.place(oloop)
    c.iload(i_slot)
    c.iconst(rows + 1)
    c.if_icmp("ge", odone)
    c.aload(off_slot)
    c.iload(i_slot)
    c.iload(i_slot)
    c.iconst(row_width)
    c.imul()
    c.iastore()
    c.iinc(i_slot, 1)
    c.goto(oloop)
    c.place(odone)


def build_natives(outdir: str):
    for cls, methods in NATIVE_CLASSES.items():
        cf = ClassFile(f"{PKG}/{cls}")
        for name, desc in methods:
            cf.add_native(name, desc)
        path = os.path.join(outdir, PKG, cls + ".class")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(cf.serialize())


def _row_index_family():
    """Names whose superclass chain reaches ExceptionWithRowIndex
    (inclusive): these get the (String,int) constructor so the shim
    can marshal the Python row_index attribute as a field instead of
    parsing it back out of the message text."""
    fam = {"ExceptionWithRowIndex"}
    changed = True
    while changed:
        changed = False
        for name, sup in EXCEPTION_CLASSES.items():
            if name not in fam and sup.rsplit("/", 1)[-1] in fam:
                fam.add(name)
                changed = True
    return fam


def build_exceptions(outdir: str):
    """Typed exceptions: public <init>(String) chaining to the
    superclass, thrown from the shim by Python type name.  The
    ExceptionWithRowIndex family additionally carries the row index in
    an int FIELD set by a (String,int) constructor — matching the
    reference's descriptor `public int getRowIndex()` exactly, so code
    compiled against the reference links (ADVICE r4: the long-returning
    message-parsing variant changed the method descriptor).  (Emission
    order is irrelevant: the JVM resolves superclasses lazily from
    the classpath.)"""
    row_family = _row_index_family()
    ROOT = f"{PKG}/ExceptionWithRowIndex"
    for name in EXCEPTION_CLASSES:
        sup = EXCEPTION_CLASSES[name]
        cf = ClassFile(f"{PKG}/{name}", super_name=sup, final=False,
                       major=49)
        is_root = name == "ExceptionWithRowIndex"
        if is_root:
            # private final, matching the .java source exactly
            cf.add_field("rowIndex", "I",
                         flags=ACC_PRIVATE | ACC_FINAL)
        # <init>(String): row index defaults to -1 (unknown)
        c = Code(cf.cp, max_locals=2)
        c.aload(0)
        c.aload(1)
        c.invokespecial(sup, "<init>", "(Ljava/lang/String;)V")
        if is_root:
            c.aload(0)
            c.iconst(-1)
            c.putfield(ROOT, "rowIndex", "I")
        c.return_void()
        cf.add_code_method("<init>", "(Ljava/lang/String;)V", c,
                           flags=ACC_PUBLIC)
        if name in row_family:
            # <init>(String, int): the shim's preferred constructor
            c = Code(cf.cp, max_locals=3)
            c.aload(0)
            c.aload(1)
            if is_root:
                c.invokespecial(sup, "<init>",
                                "(Ljava/lang/String;)V")
                c.aload(0)
                c.iload(2)
                c.putfield(ROOT, "rowIndex", "I")
            else:
                c.iload(2)
                c.invokespecial(sup, "<init>",
                                "(Ljava/lang/String;I)V")
            c.return_void()
            cf.add_code_method("<init>", "(Ljava/lang/String;I)V", c,
                               flags=ACC_PUBLIC)
        if is_root:
            c = Code(cf.cp, max_locals=1)
            c.aload(0)
            c.getfield(ROOT, "rowIndex", "I")
            c.ireturn()
            cf.add_code_method("getRowIndex", "()I", c,
                               flags=ACC_PUBLIC)
        path = os.path.join(outdir, PKG, name + ".class")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(cf.serialize())


def build_oom_smoke_test(outdir: str):
    """OomSmokeTest: a REAL JVM catch of the typed OOM exceptions the
    runtime's state machine throws across JNI (reference
    RmmSparkTest.testBasicBUFN-style forced-OOM flow).  Emitted at
    class-file major 49 so try/catch needs no StackMapTable."""
    J = f"{PKG}/"
    cf = ClassFile(f"{PKG}/OomSmokeTest", major=49)
    c = Code(cf.cp, max_locals=8)

    c.aload(0)
    c.iconst(0)
    c.aaload()
    c.invokestatic("java/lang/System", "load", "(Ljava/lang/String;)V")
    c.invokestatic(J + "TpuRuntime", "initialize", "()V")
    c.lconst(1 << 20)
    c.invokestatic(J + "RmmSpark", "setEventHandler", "(J)V")
    c.lconst(1)
    c.invokestatic(J + "RmmSpark", "currentThreadIsDedicatedToTask",
                   "(J)V")
    TID = 2
    c.invokestatic(J + "RmmSpark", "getCurrentThreadId", "()J")
    c.lstore(TID)

    def forced_oom_block(force_method, exc_cls, msg):
        c.lload(TID)
        c.iconst(1)
        c.invokestatic(J + "RmmSpark", force_method, "(JI)V")
        t_start, t_end, handler, after = (Label(), Label(), Label(),
                                          Label())
        c.place(t_start)
        c.lconst(64)
        c.invokestatic(J + "RmmSpark", "alloc", "(J)V")
        c.iconst(0)
        c.ldc_string("expected " + exc_cls + " was not thrown")
        c.invokestatic(J + "TestSupport", "assertTrue",
                       "(ILjava/lang/String;)V")
        c.place(t_end)
        c.goto(after)
        c.place(handler)
        c.handler_entry()
        c.astore(4)
        c.println(msg)
        c.place(after)
        c.try_catch(t_start, t_end, handler, J + exc_cls)
        # retry contract: park until ready, then the retry succeeds
        c.invokestatic(J + "RmmSpark", "blockThreadUntilReady", "()V")
        c.lconst(64)
        c.invokestatic(J + "RmmSpark", "alloc", "(J)V")
        c.lconst(64)
        c.invokestatic(J + "RmmSpark", "dealloc", "(J)V")

    forced_oom_block("forceRetryOOM", "GpuRetryOOM",
                     "caught GpuRetryOOM across JNI")
    forced_oom_block("forceSplitAndRetryOOM", "GpuSplitAndRetryOOM",
                     "caught GpuSplitAndRetryOOM across JNI")

    # ANSI cast error: Python raises CastException; catching the Java
    # SUPERCLASS ExceptionWithRowIndex proves the emitted hierarchy
    BADCOL = 5
    c.string_array(["12", "boom"])
    c.invokestatic(J + "TpuColumns", "fromStrings",
                   "([Ljava/lang/String;)J")
    c.lstore(BADCOL)
    t_start, t_end, handler, after = (Label(), Label(), Label(),
                                      Label())
    c.place(t_start)
    c.lload(BADCOL)
    c.iconst(1)                  # ansi=true
    c.iconst(1)                  # strip=true
    c.ldc_string("int32")
    c.invokestatic(J + "CastStrings", "toInteger",
                   "(JZZLjava/lang/String;)J")
    c.pop2_op()                  # discard the (never-produced) handle
    c.iconst(0)
    c.ldc_string("expected CastException was not thrown")
    c.invokestatic(J + "TestSupport", "assertTrue",
                   "(ILjava/lang/String;)V")
    c.place(t_end)
    c.goto(after)
    c.place(handler)
    c.handler_entry()
    c.astore(4)
    # the typed exception's API works too: the shim marshalled the
    # Python row_index attribute into the int field (no message parse)
    rownum_ok = Label()
    c.aload(4)
    c.invokevirtual(J + "ExceptionWithRowIndex", "getRowIndex", "()I")
    c.iconst(1)
    c.if_icmp("eq", rownum_ok)
    c.iconst(0)
    c.ldc_string("getRowIndex() != 1 for the ANSI cast error")
    c.invokestatic(J + "TestSupport", "assertTrue",
                   "(ILjava/lang/String;)V")
    c.place(rownum_ok)
    c.println("caught ExceptionWithRowIndex (ANSI cast) across JNI")
    c.place(after)
    c.try_catch(t_start, t_end, handler,
                J + "ExceptionWithRowIndex")
    c.lload(BADCOL)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")

    c.lconst(1)
    c.invokestatic(J + "RmmSpark", "taskDone", "(J)V")
    c.invokestatic(J + "RmmSpark", "clearEventHandler", "()V")
    c.println("OOM smoke: ALL OK")
    c.return_void()
    cf.add_code_method("main", "([Ljava/lang/String;)V", c)

    path = os.path.join(outdir, PKG, "OomSmokeTest.class")
    with open(path, "wb") as f:
        f.write(cf.serialize())


def build_smoke_test(outdir: str, xx_gold):
    """JniSmokeTest.main: mostly straight-line bytecode (assertions
    throw from native TestSupport.assertTrue); the bulk-string section
    carries fill loops, so the class is emitted at major 49 where
    branches need no StackMapTable."""
    cf = ClassFile(f"{PKG}/JniSmokeTest", major=49)
    c = Code(cf.cp, max_locals=80)
    J = f"{PKG}/"

    def assert_check(msg):
        c.ldc_string(msg)
        c.invokestatic(J + "TestSupport", "assertTrue",
                       "(ILjava/lang/String;)V")

    # System.load(args[0])  — absolute path to the shim .so
    c.aload(0)
    c.iconst(0)
    c.aaload()
    c.invokestatic("java/lang/System", "load", "(Ljava/lang/String;)V")
    c.invokestatic(J + "TpuRuntime", "initialize", "()V")
    c.println("runtime initialized")

    # --- murmur3 against Spark-derived goldens -----------------------
    H_STR = 2        # locals: 2=strings col, 4=murmur col
    c.string_array(MURMUR_IN)
    c.invokestatic(J + "TpuColumns", "fromStrings",
                   "([Ljava/lang/String;)J")
    c.lstore(H_STR)
    c.iconst(42)
    c.long_array_locals([H_STR])
    c.invokestatic(J + "Hash", "murmurHash32", "(I[J)J")
    c.lstore(4)
    c.lload(4)
    c.int_array(MURMUR_GOLD)
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("murmur3_32 Spark golden")
    c.println("murmur3_32 golden ok")

    # --- xxhash64 ----------------------------------------------------
    H_LONGS = 6      # 6=int64 col, 8=xxhash col
    c.long_array_consts([1, 2, 3])
    c.invokestatic(J + "TpuColumns", "fromLongs", "([J)J")
    c.lstore(H_LONGS)
    c.lconst(42)
    c.long_array_locals([H_LONGS])
    c.invokestatic(J + "Hash", "xxHash64", "(J[J)J")
    c.lstore(8)
    c.lload(8)
    c.long_array_consts(xx_gold)
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("xxhash64 engine golden")
    c.println("xxhash64 golden ok")

    # --- row conversion round trip ----------------------------------
    ROWS, BACK_ARR, BACK0 = 10, 12, 13
    c.long_array_locals([H_LONGS])
    c.invokestatic(J + "RowConversion", "convertToRows", "([J)J")
    c.lstore(ROWS)
    c.lload(ROWS)
    c.string_array(["int64"])
    c.int_array([0])
    c.invokestatic(J + "RowConversion", "convertFromRows",
                   "(J[Ljava/lang/String;[I)[J")
    c.astore(BACK_ARR)
    c.aload(BACK_ARR)
    c.iconst(0)
    c.laload()
    c.lstore(BACK0)
    c.lload(H_LONGS)
    c.lload(BACK0)
    c.invokestatic(J + "TestSupport", "checkColumnsEqual", "(JJ)I")
    assert_check("JCUDF row conversion round trip")
    c.println("row conversion round trip ok")

    # --- cast string -> int32 ---------------------------------------
    H_NUM, H_CAST = 15, 17
    c.string_array(["123", "-45", "999"])
    c.invokestatic(J + "TpuColumns", "fromStrings",
                   "([Ljava/lang/String;)J")
    c.lstore(H_NUM)
    c.lload(H_NUM)
    c.iconst(0)          # ansi=false
    c.iconst(1)          # strip=true
    c.ldc_string("int32")
    c.invokestatic(J + "CastStrings", "toInteger",
                   "(JZZLjava/lang/String;)J")
    c.lstore(H_CAST)
    c.lload(H_CAST)
    c.int_array([123, -45, 999])
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("CastStrings.toInteger")
    c.println("cast string->int ok")

    # --- get_json_object --------------------------------------------
    H_JSON, H_JOUT = 19, 21
    c.string_array(['{"a": 1}', '{"a": 2}'])
    c.invokestatic(J + "TpuColumns", "fromStrings",
                   "([Ljava/lang/String;)J")
    c.lstore(H_JSON)
    c.lload(H_JSON)
    c.ldc_string("$.a")
    c.invokestatic(J + "JSONUtils", "getJsonObject",
                   "(JLjava/lang/String;)J")
    c.lstore(H_JOUT)
    c.lload(H_JOUT)
    c.string_array(["1", "2"])
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("JSONUtils.getJsonObject")
    c.println("get_json_object ok")

    # --- ParseURI over the device engine -----------------------------
    H_URI, H_HOST = 25, 27
    c.string_array(["https://h.example.com/p?a=1"])
    c.invokestatic(J + "TpuColumns", "fromStrings",
                   "([Ljava/lang/String;)J")
    c.lstore(H_URI)
    c.lload(H_URI)
    c.iconst(0)
    c.invokestatic(J + "ParseURI", "parseHost", "(JZ)J")
    c.lstore(H_HOST)
    c.lload(H_HOST)
    c.string_array(["h.example.com"])
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("ParseURI.parseHost")
    c.println("parse_uri ok")

    # --- Kudo serializer round trip over the JNI byte[] boundary -----
    KB, MERGED, MERGED0 = 29, 30, 31
    c.long_array_locals([H_LONGS])
    c.iconst(0)
    c.iconst(3)
    c.invokestatic(J + "KudoSerializer", "writeToStream", "([JII)[B")
    c.astore(KB)
    c.aload(KB)
    c.string_array(["int64"])
    c.int_array([0])
    c.invokestatic(J + "KudoSerializer", "mergeToTable",
                   "([B[Ljava/lang/String;[I)[J")
    c.astore(MERGED)
    c.aload(MERGED)
    c.iconst(0)
    c.laload()
    c.lstore(MERGED0)
    c.lload(H_LONGS)
    c.lload(MERGED0)
    c.invokestatic(J + "TestSupport", "checkColumnsEqual", "(JJ)I")
    assert_check("Kudo write/merge over JNI")
    c.println("kudo round trip ok")

    # --- native host-table kudo (pure C++, GIL-free): byte parity
    # with the Python engine + merge round trip --------------------
    NHT, NB, NB1, NB2, NCAT, NMERGED, NCOLS, NM0 = (
        60, 62, 63, 64, 65, 66, 68, 69)
    c.long_array_locals([H_LONGS])
    c.invokestatic(J + "KudoSerializer", "hostTableFromColumns",
                   "([J)J")
    c.lstore(NHT)
    c.lload(NHT)
    c.iconst(0)
    c.iconst(3)
    c.invokestatic(J + "KudoSerializer", "writeHostTable", "(JII)[B")
    c.astore(NB)
    c.aload(NB)
    c.aload(KB)
    c.invokestatic("java/util/Arrays", "equals", "([B[B)Z")
    assert_check("native kudo bytes != python kudo bytes")
    # two partitions, concatenated
    c.lload(NHT)
    c.iconst(0)
    c.iconst(2)
    c.invokestatic(J + "KudoSerializer", "writeHostTable", "(JII)[B")
    c.astore(NB1)
    c.lload(NHT)
    c.iconst(2)
    c.iconst(1)
    c.invokestatic(J + "KudoSerializer", "writeHostTable", "(JII)[B")
    c.astore(NB2)
    c.aload(NB1)
    c.arraylength()
    c.aload(NB2)
    c.arraylength()
    c.iadd()
    c.newarray(8)            # T_BYTE
    c.astore(NCAT)
    c.aload(NB1)
    c.iconst(0)
    c.aload(NCAT)
    c.iconst(0)
    c.aload(NB1)
    c.arraylength()
    c.invokestatic("java/lang/System", "arraycopy",
                   "(Ljava/lang/Object;ILjava/lang/Object;II)V")
    c.aload(NB2)
    c.iconst(0)
    c.aload(NCAT)
    c.aload(NB1)
    c.arraylength()
    c.aload(NB2)
    c.arraylength()
    c.invokestatic("java/lang/System", "arraycopy",
                   "(Ljava/lang/Object;ILjava/lang/Object;II)V")
    # native merge, then the merged table's full rewrite must equal
    # the original full-range write (buffers/masks/offsets rebuilt)
    c.aload(NCAT)
    c.lload(NHT)
    c.invokestatic(J + "KudoSerializer", "mergeToHostTable", "([BJ)J")
    c.lstore(NMERGED)
    c.lload(NMERGED)
    c.iconst(0)
    c.iconst(3)
    c.invokestatic(J + "KudoSerializer", "writeHostTable", "(JII)[B")
    c.aload(NB)
    c.invokestatic("java/util/Arrays", "equals", "([B[B)Z")
    assert_check("native merged rewrite != full write")
    # merged host table -> runtime columns -> equals original
    c.lload(NMERGED)
    c.invokestatic(J + "KudoSerializer", "hostTableToColumns",
                   "(J)[J")
    c.astore(NCOLS)
    c.aload(NCOLS)
    c.iconst(0)
    c.laload()
    c.lstore(NM0)
    c.lload(H_LONGS)
    c.lload(NM0)
    c.invokestatic(J + "TestSupport", "checkColumnsEqual", "(JJ)I")
    assert_check("native merged columns != original")
    c.lload(NHT)
    c.invokestatic(J + "KudoSerializer", "freeHostTable", "(J)V")
    c.lload(NMERGED)
    c.invokestatic(J + "KudoSerializer", "freeHostTable", "(J)V")
    c.println("native kudo host-table ok")

    # --- HostTable spill round trip ---------------------------------
    HT, RESTORED, RESTORED0 = 33, 35, 36
    c.long_array_locals([H_LONGS])
    c.invokestatic(J + "HostTable", "fromTable", "([J)J")
    c.lstore(HT)
    c.lload(HT)
    c.invokestatic(J + "HostTable", "toDeviceColumns", "(J)[J")
    c.astore(RESTORED)
    c.aload(RESTORED)
    c.iconst(0)
    c.laload()
    c.lstore(RESTORED0)
    c.lload(H_LONGS)
    c.lload(RESTORED0)
    c.invokestatic(J + "TestSupport", "checkColumnsEqual", "(JJ)I")
    assert_check("HostTable spill round trip")
    c.lload(HT)
    c.invokestatic(J + "HostTable", "free", "(J)V")
    c.println("host table spill ok")

    # --- JoinPrimitives: [1,2,3] inner-join [2,3,4] ------------------
    H_RK, JP, JP0, JP1 = 38, 40, 41, 43
    c.long_array_consts([2, 3, 4])
    c.invokestatic(J + "TpuColumns", "fromLongs", "([J)J")
    c.lstore(H_RK)
    c.long_array_locals([H_LONGS])
    c.long_array_locals([H_RK])
    c.iconst(1)
    c.invokestatic(J + "JoinPrimitives", "sortMergeInnerJoin",
                   "([J[JZ)[J")
    c.astore(JP)
    c.aload(JP)
    c.iconst(0)
    c.laload()
    c.lstore(JP0)
    c.aload(JP)
    c.iconst(1)
    c.laload()
    c.lstore(JP1)
    c.lload(JP0)
    c.int_array([1, 2])          # keys 2,3 match at left rows 1,2
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("JoinPrimitives left indices")
    c.lload(JP1)
    c.int_array([0, 1])
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("JoinPrimitives right indices")
    c.println("join primitives ok")

    # --- BloomFilter: no false negatives on inserted keys ------------
    BF, BF2, PRB = 45, 47, 49
    c.iconst(3)
    c.iconst(4)
    c.iconst(2)
    c.invokestatic(J + "BloomFilter", "create", "(III)J")
    c.lstore(BF)
    c.lload(BF)
    c.lload(H_LONGS)
    c.invokestatic(J + "BloomFilter", "put", "(JJ)J")
    c.lstore(BF2)
    c.lload(BF2)
    c.lload(H_LONGS)
    c.invokestatic(J + "BloomFilter", "probe", "(JJ)J")
    c.lstore(PRB)
    c.lload(PRB)
    c.int_array([1, 1, 1])
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("BloomFilter probe: inserted keys all hit")
    c.println("bloom filter ok")

    # --- Arithmetic.multiply + JSONUtils multi-path ------------------
    H_ML, H_MP, H_MP0 = 51, 53, 54
    c.lload(H_LONGS)               # [1,2,3]
    c.lload(H_RK)                  # [2,3,4]
    c.iconst(0)
    c.iconst(0)
    c.invokestatic(J + "Arithmetic", "multiply", "(JJZZ)J")
    c.lstore(H_ML)
    c.lload(H_ML)
    c.long_array_consts([2, 6, 12])
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("Arithmetic.multiply")
    c.lload(H_JSON)                # ['{"a": 1}', '{"a": 2}']
    c.string_array(["$.a"])
    c.lconst(-1)
    c.iconst(-1)
    c.invokestatic(J + "JSONUtils", "getJsonObjectMultiplePaths",
                   "(J[Ljava/lang/String;JI)[J")
    c.astore(H_MP)
    c.aload(H_MP)
    c.iconst(0)
    c.laload()
    c.lstore(H_MP0)
    c.lload(H_MP0)
    c.string_array(["1", "2"])
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("JSONUtils.getJsonObjectMultiplePaths")
    c.println("arithmetic + multi-path json ok")

    # --- StringUtils.randomUUIDs ------------------------------------
    H_UUID = 23
    c.iconst(4)
    c.lconst(1)
    c.invokestatic(J + "StringUtils", "randomUUIDs", "(IJ)J")
    c.lstore(H_UUID)
    c.println("randomUUIDs ok")

    # --- Profiler lifecycle with a file sink -------------------------
    H_PF = 56
    c.ldc_string("/tmp/jni_profile.bin")
    c.iconst(0)
    c.iconst(1)
    c.invokestatic(J + "Profiler", "nativeInit",
                   "(Ljava/lang/String;IZ)V")
    c.invokestatic(J + "Profiler", "nativeStart", "()V")
    c.long_array_consts([7, 8])
    c.invokestatic(J + "TpuColumns", "fromLongs", "([J)J")
    c.lstore(H_PF)
    c.lload(H_PF)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.invokestatic(J + "Profiler", "nativeStop", "()V")
    c.invokestatic(J + "Profiler", "nativeShutdown", "()V")
    c.println("profiler lifecycle ok")

    # --- DecimalUtils.multiply128 over fromDecimals ------------------
    H_DA, H_DB, H_DR, H_DR0, H_DR1 = 58, 60, 62, 63, 65
    c.long_array_consts([125, 250])
    c.iconst(-2)
    c.ldc_string("decimal128")
    c.invokestatic(J + "TpuColumns", "fromDecimals",
                   "([JILjava/lang/String;)J")
    c.lstore(H_DA)
    c.long_array_consts([200, 400])
    c.iconst(-2)
    c.ldc_string("decimal128")
    c.invokestatic(J + "TpuColumns", "fromDecimals",
                   "([JILjava/lang/String;)J")
    c.lstore(H_DB)
    c.lload(H_DA)
    c.lload(H_DB)
    c.iconst(-4)
    c.invokestatic(J + "DecimalUtils", "multiply128", "(JJI)[J")
    c.astore(H_DR)
    c.aload(H_DR)
    c.iconst(0)
    c.laload()
    c.lstore(H_DR0)                # overflow flags
    c.aload(H_DR)
    c.iconst(1)
    c.laload()
    c.lstore(H_DR1)                # product (unscaled)
    c.lload(H_DR1)
    c.long_array_consts([25000, 100000])
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("DecimalUtils.multiply128")
    c.lload(H_DR0)
    c.int_array([0, 0])
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("DecimalUtils.multiply128 overflow flags clear")
    c.invokestatic(J + "DeviceAttr", "isIntegratedGPU", "()Z")
    c.ldc_string("DeviceAttr.isIntegratedGPU (true on CPU backend)")
    c.invokestatic(J + "TestSupport", "assertTrue",
                   "(ILjava/lang/String;)V")
    c.println("decimal128 multiply ok")

    # --- RmmSpark facade over the OOM state machine ------------------
    c.lconst(1 << 20)
    c.invokestatic(J + "RmmSpark", "setEventHandler", "(J)V")
    c.lconst(99)
    c.lconst(1)
    c.invokestatic(J + "RmmSpark", "startDedicatedTaskThread", "(JJ)V")
    c.lconst(1)
    c.invokestatic(J + "RmmSpark", "taskDone", "(J)V")
    c.invokestatic(J + "RmmSpark", "clearEventHandler", "()V")
    c.println("RmmSpark register/taskDone ok")

    # --- GpuExec-shaped composition: join -> gather -> aggregate, all
    # through JVM handles (the north-star calling pattern) ----------
    MQPAIRS, MQL, MQLI, MQRI, MQGV = 71, 72, 74, 76, 78
    # (past every section still live at hygiene time; reused later by
    # the list/bulk/cudf sections after these frees)
    c.long_array_consts([10, 20, 30])         # left values keyed 1,2,3
    c.invokestatic(J + "TpuColumns", "fromLongs", "([J)J")
    c.lstore(MQL)
    # join left keys [1,2,3] (H_LONGS) with right keys [2,3,4] (H_RK)
    c.long_array_locals([H_LONGS])
    c.long_array_locals([H_RK])
    c.iconst(0)
    c.invokestatic(J + "JoinPrimitives", "sortMergeInnerJoin",
                   "([J[JZ)[J")
    c.astore(MQPAIRS)
    c.aload(MQPAIRS)
    c.iconst(0)
    c.laload()
    c.lstore(MQLI)
    c.aload(MQPAIRS)
    c.iconst(1)
    c.laload()
    c.lstore(MQRI)
    # gather the left values at the join's left indices -> [20, 30]
    c.lload(MQL)
    c.lload(MQLI)
    c.invokestatic(J + "TpuColumns", "gather", "(JJ)J")
    c.lstore(MQGV)
    c.lload(MQGV)
    c.long_array_consts([20, 30])
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("join->gather composition")
    c.lload(MQL)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.lload(MQLI)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.lload(MQRI)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.lload(MQGV)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.println("join->gather composition ok")

    # --- HLL++ sketch reduce/estimate over JNI (golden from the
    # Python engine at emission time — deterministic) ---------------
    from spark_rapids_tpu.columns import dtypes as _dt
    from spark_rapids_tpu.columns.column import Column as _Col
    from spark_rapids_tpu.ops import hllpp as _hll
    _hcol = _Col.from_pylist(list(range(200)), _dt.INT64)
    _est = int(_hll.estimate_from_hll_sketches(
        _hll.reduce_hllpp(_hcol, 9), 9).to_pylist()[0])
    HLC, HLS, HLE = 72, 74, 76
    c.long_array_consts(list(range(200)))
    c.invokestatic(J + "TpuColumns", "fromLongs", "([J)J")
    c.lstore(HLC)
    c.lload(HLC)
    c.iconst(9)
    c.invokestatic(J + "HyperLogLogPlusPlusHostUDF", "reduce",
                   "(JI)J")
    c.lstore(HLS)
    c.lload(HLS)
    c.iconst(9)
    c.invokestatic(J + "HyperLogLogPlusPlusHostUDF", "estimate",
                   "(JI)J")
    c.lstore(HLE)
    c.lload(HLE)
    c.long_array_consts([_est])
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("HLL++ estimate golden")
    for slot in (HLC, HLS, HLE):
        c.lload(slot)
        c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.println("hllpp reduce/estimate ok (golden %d)" % _est)

    _emit_surface_sweep(c, J, assert_check, H_LONGS, H_NUM, H_STR,
                        H_URI, H_DA, H_DB, BF, BF2)

    # --- list slice + ORC tz + device telemetry surface (r5) --------
    LSTC, SLICED = 72, 74     # long slots 72-73, 74-75 (past all
    #                            sections still live at hygiene time)
    c.int_array([0, 3, 5])
    c.long_array_consts([1, 2, 3, 4, 5])
    c.invokestatic(J + "TestSupport", "makeListOfInts", "([I[J)J")
    c.lstore(LSTC)
    c.lload(LSTC)
    c.iconst(1)                    # start (1-based)
    c.iconst(2)                    # length
    c.iconst(1)                    # checkStartLength = true
    c.invokestatic(J + "GpuListSliceUtils", "listSlice", "(JIIZ)J")
    c.lstore(SLICED)
    c.lload(LSTC)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.int_array([0, 2, 4])         # expected [[1,2],[4,5]]
    c.long_array_consts([1, 2, 4, 5])
    c.invokestatic(J + "TestSupport", "makeListOfInts", "([I[J)J")
    c.lstore(LSTC)
    c.lload(SLICED)
    c.lload(LSTC)
    c.invokestatic(J + "TestSupport", "checkColumnsEqual", "(JJ)I")
    assert_check("GpuListSliceUtils.listSlice")
    c.lload(LSTC)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.lload(SLICED)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    # ORC timezone rule extraction: UTC packs [raw=0, dst=0, n=0]
    c.ldc_string("UTC")
    c.invokestatic(J + "OrcDstRuleExtractor", "timezoneInfoPacked",
                   "(Ljava/lang/String;)[J")
    c.arraylength()
    c.iconst(3)
    c.idiv()                       # len/3: 0 for len<3, >=1 otherwise
    assert_check("OrcDstRuleExtractor.timezoneInfoPacked")
    # device telemetry: at least one device visible
    c.invokestatic(J + "nvml/NVML", "getDeviceCount", "()I")
    assert_check("NVML.getDeviceCount >= 1")
    c.println("list/tz/telemetry surface ok")

    # --- bulk string path: content parity with the boxed path, and a
    # 10MB single-crossing round trip (VERDICT r4 weak #4) ----------
    BCH, BOF, BH, BH2 = 76, 77, 78, 72   # 78-79 + reuse 72-73
    # small: boxed vs bulk build of the same ["ab","c","","dd"]
    c.string_array(["ab", "c", "", "dd"])
    c.invokestatic(J + "TpuColumns", "fromStrings",
                   "([Ljava/lang/String;)J")
    c.lstore(BH2)
    c.iconst(5)
    c.newarray(8)                  # byte[] "abcdd"
    c.astore(BCH)
    for i, ch in enumerate(b"abcdd"):
        c.aload(BCH)
        c.iconst(i)
        c.iconst(ch)
        c.bastore()
    c.aload(BCH)
    c.int_array([0, 2, 3, 3, 5])
    c.aconst_null()
    c.invokestatic(J + "TpuColumns", "fromStringsBulk", "([B[I[B)J")
    c.lstore(BH)
    c.lload(BH2)
    c.lload(BH)
    c.invokestatic(J + "TestSupport", "checkColumnsEqual", "(JJ)I")
    assert_check("bulk string build != boxed build")
    c.lload(BH2)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    # bulk offsets readback: little-endian bytes of [0,2,3,3,5]
    c.lload(BH)
    c.invokestatic(J + "TpuColumns", "getStringOffsets", "(J)[B")
    c.iconst(20)
    c.newarray(8)
    c.astore(BCH)
    for pos, val in ((4, 2), (8, 3), (12, 3), (16, 5)):
        c.aload(BCH)
        c.iconst(pos)
        c.iconst(val)
        c.bastore()
    c.aload(BCH)
    c.invokestatic("java/util/Arrays", "equals", "([B[B)Z")
    assert_check("bulk offsets readback != expected LE bytes")
    c.lload(BH)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    # big: 10MB chars, 500k rows of 20 bytes, one crossing each way
    _emit_bulk_string_arrays(c, BCH, BOF, 71, 97)
    c.aload(BCH)
    c.aload(BOF)
    c.aconst_null()
    c.invokestatic(J + "TpuColumns", "fromStringsBulk", "([B[I[B)J")
    c.lstore(BH)
    c.lload(BH)
    c.invokestatic(J + "TpuColumns", "getStringChars", "(J)[B")
    c.aload(BCH)
    c.invokestatic("java/util/Arrays", "equals", "([B[B)Z")
    assert_check("10MB bulk chars round trip")
    c.lload(BH)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.println("bulk string path ok")

    # --- ai.rapids.cudf handle shapes (plugin calling convention) ---
    CVEC = "ai/rapids/cudf/ColumnVector"
    TBL = "ai/rapids/cudf/Table"
    CUV, CUARR, CUT = 76, 77, 79   # vector ref / array ref / table
    c.long_array_consts([1, 2, 3])
    c.invokestatic(J + "TpuColumns", "fromLongs", "([J)J")
    c.lstore(72)                 # expected column for equality
    c.string_array(["1", "2", "3"])
    c.invokestatic(CVEC, "fromStrings",
                   "([Ljava/lang/String;)L" + CVEC + ";")
    c.astore(CUV)
    c.iconst(1)
    c.anewarray(CVEC)
    c.dup()
    c.iconst(0)
    c.aload(CUV)
    c.aastore()
    c.astore(CUARR)
    c.new_obj(TBL)
    c.dup()
    c.aload(CUARR)
    c.invokespecial(TBL, "<init>", "([L" + CVEC + ";)V")
    c.astore(CUT)
    # cast the table's column through a real op: the handle bundle is
    # what GpuExec-shaped code passes into the jni classes
    c.aload(CUT)
    c.invokevirtual(TBL, "getNativeHandles", "()[J")
    c.iconst(0)
    c.laload()
    c.iconst(0)                  # ansi=false
    c.iconst(1)                  # strip=true
    c.ldc_string("int64")
    c.invokestatic(J + "CastStrings", "toInteger",
                   "(JZZLjava/lang/String;)J")
    c.lstore(74)
    c.lload(74)
    c.lload(72)
    c.invokestatic(J + "TestSupport", "checkColumnsEqual", "(JJ)I")
    assert_check("cudf Table handle bundle through CastStrings")
    c.lload(74)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.lload(72)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.aload(CUT)
    c.invokevirtual(TBL, "close", "()V")
    c.println("cudf handle shapes ok")

    # --- handle hygiene ----------------------------------------------
    for h in [H_STR, 4, H_LONGS, 8, ROWS, BACK0, H_NUM, H_CAST,
              H_JSON, H_JOUT, H_UUID, H_URI, H_HOST, MERGED0, NM0,
              RESTORED0, H_RK, JP0, JP1, BF, BF2, PRB, H_ML,
              H_MP0, H_DA, H_DB, H_DR0, H_DR1]:
        c.lload(h)
        c.invokestatic(J + "TpuColumns", "free", "(J)V")
    # leak check: every handle any section created must be freed
    no_leak = Label()
    c.invokestatic(J + "TpuRuntime", "liveHandles", "()I")
    c.ifeq_lbl(no_leak)
    c.iconst(0)
    c.ldc_string("handle leak: liveHandles != 0 before shutdown")
    c.invokestatic(J + "TestSupport", "assertTrue",
                   "(ILjava/lang/String;)V")
    c.place(no_leak)
    c.println("handle hygiene: zero leaks")
    c.invokestatic(J + "TpuRuntime", "shutdown", "()V")

    c.println("JNI smoke: ALL OK")
    c.return_void()
    cf.add_code_method("main", "([Ljava/lang/String;)V", c)

    path = os.path.join(outdir, PKG, "JniSmokeTest.class")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(cf.serialize())



def build_bufn_smoke_test(outdir: str):
    """BufnSmokeTest: TWO REAL JVM THREADS driven into the BUFN
    deadlock-break cycle through the JNI surface (reference
    RmmSparkTest.testBasicBUFN:1002 / docs/memory_management.md flow;
    Python spec: tests/test_rmm_spark.py test_bufn_and_split_full
    _cycle).  Main = task 1 (higher priority), worker = task 2:

      both hold/request 600 of a 1000-byte budget -> worker blocks ->
      main blocks -> deadlock -> worker (lowest priority) rolls back
      with GpuRetryOOM and parks BUFN -> main retries once, rolls back
      with GpuRetryOOM, frees, parks -> all BUFN -> main (highest
      priority) is the split-and-retry victim (GpuSplitAndRetryOOM)
      and completes with two half allocations -> worker wakes and
      finishes.

    Plus the pool/shuffle thread registration path
    (shuffleThreadWorkingOnTasks / poolThreadFinishedForTasks).
    Emitted at major 49 (branches, try/catch without StackMapTable).
    """
    J = f"{PKG}/"
    W = f"{PKG}/BufnWorker"

    # ---- worker: extends Thread -------------------------------------
    cf = ClassFile(W, super_name="java/lang/Thread", final=False,
                   major=49)
    cf.add_field("tid", "J", flags=ACC_PUBLIC | ACC_VOLATILE)
    cf.add_field("mode", "I", flags=ACC_PUBLIC | ACC_VOLATILE)
    cf.add_field("gotRetry", "I", flags=ACC_PUBLIC | ACC_VOLATILE)
    cf.add_field("done", "I", flags=ACC_PUBLIC | ACC_VOLATILE)
    c = Code(cf.cp, max_locals=1)
    c.aload(0)
    c.invokespecial("java/lang/Thread", "<init>", "()V")
    c.return_void()
    cf.add_code_method("<init>", "()V", c, flags=ACC_PUBLIC)

    c = Code(cf.cp, max_locals=4)      # 0=this 1-2=tid 3=scratch
    shuffle_mode, task_end = Label(), Label()
    c.aload(0)
    c.getfield(W, "mode", "I")
    c.iconst(1)
    c.if_icmp("eq", shuffle_mode)
    # ---- mode 0: the BUFN task-2 side ----
    c.invokestatic(J + "RmmSpark", "getCurrentThreadId", "()J")
    c.lstore(1)
    c.aload(0)
    c.lload(1)
    c.putfield(W, "tid", "J")
    c.lload(1)
    c.lconst(2)
    c.invokestatic(J + "RmmSpark", "startDedicatedTaskThread",
                   "(JJ)V")
    t0, t1, hdl, after = Label(), Label(), Label(), Label()
    c.place(t0)
    c.lconst(600)
    c.invokestatic(J + "RmmSpark", "alloc", "(J)V")
    c.place(t1)
    c.goto(after)
    c.place(hdl)
    c.handler_entry()
    c.pop_op()                         # discard the exception ref
    c.aload(0)
    c.iconst(1)
    c.putfield(W, "gotRetry", "I")
    c.place(after)
    c.try_catch(t0, t1, hdl, J + "GpuRetryOOM")
    # retry framework: park BUFN until task 1 finishes, then complete
    c.invokestatic(J + "RmmSpark", "blockThreadUntilReady", "()V")
    c.lconst(600)
    c.invokestatic(J + "RmmSpark", "alloc", "(J)V")
    c.lconst(600)
    c.invokestatic(J + "RmmSpark", "dealloc", "(J)V")
    c.lconst(2)
    c.invokestatic(J + "RmmSpark", "taskDone", "(J)V")
    c.aload(0)
    c.iconst(1)
    c.putfield(W, "done", "I")
    c.goto(task_end)
    # ---- mode 1: pool/shuffle thread registration path ----
    c.place(shuffle_mode)
    c.long_array_consts([5])
    c.invokestatic(J + "RmmSpark", "shuffleThreadWorkingOnTasks",
                   "([J)V")
    c.lconst(100)
    c.invokestatic(J + "RmmSpark", "alloc", "(J)V")
    c.lconst(100)
    c.invokestatic(J + "RmmSpark", "dealloc", "(J)V")
    c.long_array_consts([5])
    c.invokestatic(J + "RmmSpark", "poolThreadFinishedForTasks",
                   "([J)V")
    c.aload(0)
    c.iconst(1)
    c.putfield(W, "done", "I")
    c.place(task_end)
    c.return_void()
    c.max_stack = max(c.max_stack, 8)
    cf.add_code_method("run", "()V", c, flags=ACC_PUBLIC)
    path = os.path.join(outdir, PKG, "BufnWorker.class")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(cf.serialize())

    # ---- driver -----------------------------------------------------
    cf = ClassFile(f"{PKG}/BufnSmokeTest", major=49)
    c = Code(cf.cp, max_locals=16)
    # 0=args 1=w(ref) 2-3=tid1 4=flag 5=w2(ref)

    def assert_check(msg):
        c.ldc_string(msg)
        c.invokestatic(J + "TestSupport", "assertTrue",
                       "(ILjava/lang/String;)V")

    c.aload(0)
    c.iconst(0)
    c.aaload()
    c.invokestatic("java/lang/System", "load", "(Ljava/lang/String;)V")
    c.invokestatic(J + "TpuRuntime", "initialize", "()V")
    c.lconst(1000)
    c.invokestatic(J + "RmmSpark", "setEventHandler", "(J)V")
    c.invokestatic(J + "RmmSpark", "getCurrentThreadId", "()J")
    c.lstore(2)
    c.lload(2)
    c.lconst(1)
    c.invokestatic(J + "RmmSpark", "startDedicatedTaskThread",
                   "(JJ)V")
    c.lconst(600)
    c.invokestatic(J + "RmmSpark", "alloc", "(J)V")
    c.new_obj(f"{PKG}/BufnWorker")
    c.dup()
    c.invokespecial(f"{PKG}/BufnWorker", "<init>", "()V")
    c.astore(1)
    c.aload(1)
    c.invokevirtual("java/lang/Thread", "start", "()V")
    # wait for the worker to publish its thread id
    pw, pw_sleep = Label(), Label()
    c.place(pw)
    c.aload(1)
    c.getfield(f"{PKG}/BufnWorker", "tid", "J")
    c.lconst(0)
    c.lcmp()
    c.ifeq_lbl(pw_sleep)
    pws_done = Label()
    c.goto(pws_done)
    c.place(pw_sleep)
    c.lconst(5)
    c.invokestatic("java/lang/Thread", "sleep", "(J)V")
    c.goto(pw)
    c.place(pws_done)
    # wait until the worker's alloc is THREAD_BLOCKED
    ps, ps_sleep, ps_done = Label(), Label(), Label()
    c.place(ps)
    c.aload(1)
    c.getfield(f"{PKG}/BufnWorker", "tid", "J")
    c.invokestatic(J + "RmmSpark", "getStateOf",
                   "(J)Ljava/lang/String;")
    c.ldc_string("THREAD_BLOCKED")
    c.invokevirtual("java/lang/String", "equals",
                    "(Ljava/lang/Object;)Z")
    c.ifeq_lbl(ps_sleep)
    c.goto(ps_done)
    c.place(ps_sleep)
    c.lconst(5)
    c.invokestatic("java/lang/Thread", "sleep", "(J)V")
    c.goto(ps)
    c.place(ps_done)
    c.println("worker blocked; forcing the deadlock")
    # main's alloc deadlocks; worker rolls back first, then main
    c.iconst(0)
    c.istore(4)
    m0, m1, mh, ma = Label(), Label(), Label(), Label()
    c.place(m0)
    c.lconst(600)
    c.invokestatic(J + "RmmSpark", "alloc", "(J)V")
    c.place(m1)
    c.goto(ma)
    c.place(mh)
    c.handler_entry()
    c.pop_op()
    c.iconst(1)
    c.istore(4)
    c.place(ma)
    c.try_catch(m0, m1, mh, J + "GpuRetryOOM")
    c.iload(4)
    assert_check("main thread did not receive GpuRetryOOM")
    c.println("main rolled back with GpuRetryOOM")
    c.lconst(600)
    c.invokestatic(J + "RmmSpark", "dealloc", "(J)V")
    # all tasks BUFN: main is highest priority -> split victim
    c.iconst(0)
    c.istore(4)
    s0, s1, sh, sa = Label(), Label(), Label(), Label()
    c.place(s0)
    c.invokestatic(J + "RmmSpark", "blockThreadUntilReady", "()V")
    c.place(s1)
    c.goto(sa)
    c.place(sh)
    c.handler_entry()
    c.pop_op()
    c.iconst(1)
    c.istore(4)
    c.place(sa)
    c.try_catch(s0, s1, sh, J + "GpuSplitAndRetryOOM")
    c.iload(4)
    assert_check("main thread was not the split-and-retry victim")
    c.println("main selected as split-and-retry victim")
    # split: complete with two half allocations
    c.lconst(300)
    c.invokestatic(J + "RmmSpark", "alloc", "(J)V")
    c.lconst(300)
    c.invokestatic(J + "RmmSpark", "alloc", "(J)V")
    c.lconst(600)
    c.invokestatic(J + "RmmSpark", "dealloc", "(J)V")
    c.lconst(1)
    c.invokestatic(J + "RmmSpark", "taskDone", "(J)V")
    c.aload(1)
    c.invokevirtual("java/lang/Thread", "join", "()V")
    c.aload(1)
    c.getfield(f"{PKG}/BufnWorker", "gotRetry", "I")
    assert_check("worker did not receive GpuRetryOOM")
    c.aload(1)
    c.getfield(f"{PKG}/BufnWorker", "done", "I")
    assert_check("worker did not complete after BUFN wake")
    c.println("BUFN deadlock-break cycle ok")
    # pool/shuffle thread registration path
    c.new_obj(f"{PKG}/BufnWorker")
    c.dup()
    c.invokespecial(f"{PKG}/BufnWorker", "<init>", "()V")
    c.astore(5)
    c.aload(5)
    c.iconst(1)
    c.putfield(f"{PKG}/BufnWorker", "mode", "I")
    c.aload(5)
    c.invokevirtual("java/lang/Thread", "start", "()V")
    c.aload(5)
    c.invokevirtual("java/lang/Thread", "join", "()V")
    c.aload(5)
    c.getfield(f"{PKG}/BufnWorker", "done", "I")
    assert_check("shuffle-thread registration path failed")
    c.println("shuffle thread registration ok")
    c.invokestatic(J + "RmmSpark", "clearEventHandler", "()V")
    c.println("BUFN smoke: ALL OK")
    c.return_void()
    c.max_stack = max(c.max_stack, 10)
    cf.add_code_method("main", "([Ljava/lang/String;)V", c)
    path = os.path.join(outdir, PKG, "BufnSmokeTest.class")
    with open(path, "wb") as f:
        f.write(cf.serialize())



def build_cudf_classes(outdir: str):
    """Runnable ai.rapids.cudf handle classes (ColumnView /
    ColumnVector / Table) so the plugin-facing call shapes are
    drivable from the JVM smoke, not just documented in .java sources.
    Emitted at major 49 (Table loops)."""
    CV = "ai/rapids/cudf/ColumnView"
    CVEC = "ai/rapids/cudf/ColumnVector"
    TBL = "ai/rapids/cudf/Table"
    J = f"{PKG}/"

    # ---- ColumnView: handle field + accessor ----
    cf = ClassFile(CV, final=False, major=49)
    cf.add_field("handle", "J")
    c = Code(cf.cp, max_locals=3)
    c.aload(0)
    c.invokespecial("java/lang/Object", "<init>", "()V")
    c.aload(0)
    c.lload(1)
    c.putfield(CV, "handle", "J")
    c.return_void()
    cf.add_code_method("<init>", "(J)V", c, flags=ACC_PUBLIC)
    c = Code(cf.cp, max_locals=1)
    c.aload(0)
    c.getfield(CV, "handle", "J")
    c.lreturn()
    cf.add_code_method("getNativeView", "()J", c, flags=ACC_PUBLIC)
    path = os.path.join(outdir, CV + ".class")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(cf.serialize())

    # ---- ColumnVector extends ColumnView: factories + close ----
    cf = ClassFile(CVEC, super_name=CV, final=False, major=49)
    c = Code(cf.cp, max_locals=3)
    c.aload(0)
    c.lload(1)
    c.invokespecial(CV, "<init>", "(J)V")
    c.return_void()
    cf.add_code_method("<init>", "(J)V", c, flags=ACC_PUBLIC)
    for fname, desc, native in (
            ("fromLongs", "([J)L" + CVEC + ";", "fromLongs"),
            ("fromStrings", "([Ljava/lang/String;)L" + CVEC + ";",
             "fromStrings")):
        arg = "[J" if fname == "fromLongs" else "[Ljava/lang/String;"
        c = Code(cf.cp, max_locals=1)
        c.new_obj(CVEC)
        c.dup()
        c.aload(0)
        c.invokestatic(J + "TpuColumns", native, "(" + arg + ")J")
        c.invokespecial(CVEC, "<init>", "(J)V")
        c.areturn()
        c.max_stack = max(c.max_stack, 6)
        cf.add_code_method(fname, desc, c)
    # close(): idempotent like the .java source (second close is a
    # no-op, not a double release across JNI)
    c = Code(cf.cp, max_locals=1)
    already = Label()
    c.aload(0)
    c.getfield(CV, "handle", "J")
    c.lconst(0)
    c.lcmp()
    c.ifeq_lbl(already)
    c.aload(0)
    c.getfield(CV, "handle", "J")
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.aload(0)
    c.lconst(0)
    c.putfield(CV, "handle", "J")
    c.place(already)
    c.return_void()
    c.max_stack = max(c.max_stack, 6)
    cf.add_code_method("close", "()V", c, flags=ACC_PUBLIC)
    path = os.path.join(outdir, CVEC + ".class")
    with open(path, "wb") as f:
        f.write(cf.serialize())

    # ---- Table: vector array + handle bundle ----
    cf = ClassFile(TBL, final=False, major=49)
    cf.add_field("columns", "[L" + CVEC + ";")
    c = Code(cf.cp, max_locals=2)
    c.aload(0)
    c.invokespecial("java/lang/Object", "<init>", "()V")
    c.aload(0)
    c.aload(1)
    c.putfield(TBL, "columns", "[L" + CVEC + ";")
    c.return_void()
    cf.add_code_method("<init>", "([L" + CVEC + ";)V", c,
                       flags=ACC_PUBLIC)
    c = Code(cf.cp, max_locals=2)
    c.aload(0)
    c.getfield(TBL, "columns", "[L" + CVEC + ";")
    c.arraylength()
    c.ireturn()
    c.max_stack = max(c.max_stack, 2)
    cf.add_code_method("getNumberOfColumns", "()I", c,
                       flags=ACC_PUBLIC)
    c = Code(cf.cp, max_locals=2)
    c.aload(0)
    c.getfield(TBL, "columns", "[L" + CVEC + ";")
    c.iload(1)
    c.aaload()
    c.areturn()
    c.max_stack = max(c.max_stack, 3)
    cf.add_code_method("getColumn", "(I)L" + CVEC + ";", c,
                       flags=ACC_PUBLIC)
    # getNativeHandles: long[] of each column's view handle
    c = Code(cf.cp, max_locals=4)  # 0=this 1=out 2=i 3=cols
    c.aload(0)
    c.getfield(TBL, "columns", "[L" + CVEC + ";")
    c.astore(3)
    c.aload(3)
    c.arraylength()
    c.newarray(T_LONG)
    c.astore(1)
    c.iconst(0)
    c.istore(2)
    loop, done = Label(), Label()
    c.place(loop)
    c.iload(2)
    c.aload(3)
    c.arraylength()
    c.if_icmp("ge", done)
    c.aload(1)
    c.iload(2)
    c.aload(3)
    c.iload(2)
    c.aaload()
    c.invokevirtual(CV, "getNativeView", "()J")
    c.lastore()
    c.iinc(2, 1)
    c.goto(loop)
    c.place(done)
    c.aload(1)
    c.areturn()
    c.max_stack = max(c.max_stack, 8)
    cf.add_code_method("getNativeHandles", "()[J", c,
                       flags=ACC_PUBLIC)
    # close(): close every vector
    c = Code(cf.cp, max_locals=4)
    c.aload(0)
    c.getfield(TBL, "columns", "[L" + CVEC + ";")
    c.astore(3)
    c.iconst(0)
    c.istore(2)
    loop2, done2 = Label(), Label()
    c.place(loop2)
    c.iload(2)
    c.aload(3)
    c.arraylength()
    c.if_icmp("ge", done2)
    c.aload(3)
    c.iload(2)
    c.aaload()
    c.invokevirtual(CVEC, "close", "()V")
    c.iinc(2, 1)
    c.goto(loop2)
    c.place(done2)
    c.return_void()
    c.max_stack = max(c.max_stack, 6)
    cf.add_code_method("close", "()V", c, flags=ACC_PUBLIC)
    path = os.path.join(outdir, TBL + ".class")
    with open(path, "wb") as f:
        f.write(cf.serialize())



def _emit_surface_sweep(c, J, assert_check, H_LONGS, H_NUM, H_STR,
                        H_URI, H_DA, H_DB, BF, BF2):
    """Drive every remaining declared native once, with goldens
    computed AT EMISSION TIME by the same runtime engines the JVM
    call reaches (the xxhash-golden pattern, generalized).  Temp
    handles live in slots 71-79 and are freed per block."""
    from spark_rapids_tpu.shim import jni_entry as _je
    from spark_rapids_tpu.shim.handles import REGISTRY as _R

    def _vals(h, release=True):
        v = _R.get(h).to_pylist()
        if release:
            _R.release(h)
        return v

    T1, T2, T3, T4 = 72, 74, 76, 78   # long slots
    REF = 71

    def free(slot):
        c.lload(slot)
        c.invokestatic(J + "TpuColumns", "free", "(J)V")

    # mirror handles for the live smoke columns
    m_longs = _je.from_longs([1, 2, 3])
    m_num = _je.from_strings(["123", "-45", "999"])
    m_uri = _je.from_strings(["https://h.example.com/p?a=1"])

    # -- fromInts round trip --
    c.int_array([7, -8])
    c.invokestatic(J + "TpuColumns", "fromInts", "([I)J")
    c.lstore(T1)
    c.lload(T1)
    c.int_array([7, -8])
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("fromInts round trip")
    free(T1)

    # -- fromDoubles -> Arithmetic.round -> fromFloat chain --
    m_d = _je.from_doubles([1.25, -2.675, 3.14159])
    m_r = _je.arithmetic_round(m_d, 1, "HALF_UP")
    m_s = _je.float_to_string(m_r)
    gold_round = _vals(m_s)
    _R.release(m_d)
    _R.release(m_r)
    # emit double[] constants: jasm lacks a double-array helper, so
    # store raw bits through long array + Double.longBitsToDouble is
    # overkill — build via newarray double + dastore with ldc2_w bits
    c.double_array([1.25, -2.675, 3.14159])
    c.invokestatic(J + "TpuColumns", "fromDoubles", "([D)J")
    c.lstore(T1)
    c.lload(T1)
    c.iconst(1)
    c.ldc_string("HALF_UP")
    c.invokestatic(J + "Arithmetic", "round",
                   "(JILjava/lang/String;)J")
    c.lstore(T2)
    c.lload(T2)
    c.invokestatic(J + "CastStrings", "fromFloat", "(J)J")
    c.lstore(T3)
    c.lload(T3)
    c.string_array(gold_round)
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("fromDoubles->round->fromFloat")
    free(T1)
    free(T2)
    free(T3)

    # -- hiveHash --
    gold_hive = _vals(_je.hive_hash([m_longs]))
    c.long_array_locals([H_LONGS])
    c.invokestatic(J + "Hash", "hiveHash", "([J)J")
    c.lstore(T1)
    c.lload(T1)
    c.int_array(gold_hive)
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("Hash.hiveHash")
    free(T1)

    # -- toFloat -> fromFloat --
    m_f = _je.string_to_float(m_num, "float64", False)
    gold_tf = _vals(_je.float_to_string(m_f))
    _R.release(m_f)
    c.lload(H_NUM)
    c.iconst(0)
    c.ldc_string("float64")
    c.invokestatic(J + "CastStrings", "toFloat",
                   "(JZLjava/lang/String;)J")
    c.lstore(T1)
    c.lload(T1)
    c.invokestatic(J + "CastStrings", "fromFloat", "(J)J")
    c.lstore(T2)
    c.lload(T2)
    c.string_array(gold_tf)
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("toFloat->fromFloat")
    free(T1)
    free(T2)

    # -- toDate --
    m_ds = _je.from_strings(["2020-01-02", "1999-12-31"])
    gold_date = _vals(_je.cast_strings_to_date(m_ds, False))
    _R.release(m_ds)
    gold_date_days = [v if isinstance(v, int) else
                      (v.toordinal() - 719163) for v in gold_date]
    c.string_array(["2020-01-02", "1999-12-31"])
    c.invokestatic(J + "TpuColumns", "fromStrings",
                   "([Ljava/lang/String;)J")
    c.lstore(T1)
    c.lload(T1)
    c.iconst(0)
    c.invokestatic(J + "CastStrings", "toDate", "(JZ)J")
    c.lstore(T2)
    c.lload(T2)
    c.int_array(gold_date_days)
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("CastStrings.toDate")
    free(T1)
    free(T2)

    # -- fromLongToBinary + formatNumber --
    gold_bin = _vals(_je.long_to_binary_string(m_longs))
    c.lload(H_LONGS)
    c.invokestatic(J + "CastStrings", "fromLongToBinary", "(J)J")
    c.lstore(T1)
    c.lload(T1)
    c.string_array(gold_bin)
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("CastStrings.fromLongToBinary")
    free(T1)
    gold_fmt = _vals(_je.format_number(m_longs, 2))
    c.lload(H_LONGS)
    c.iconst(2)
    c.invokestatic(J + "CastStrings", "formatNumber", "(JI)J")
    c.lstore(T1)
    c.lload(T1)
    c.string_array(gold_fmt)
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("CastStrings.formatNumber")
    free(T1)

    # -- histogram create + percentile (through fromFloat) --
    m_v = _je.from_longs([10, 20, 30])
    m_fq = _je.from_longs([1, 2, 1])
    m_h = _je.histogram_create(m_v, m_fq)
    m_p = _je.histogram_percentile(m_h, [0.5])   # LIST<FLOAT64>
    m_pc = _je.struct_child(m_p, 0)
    gold_pct = _vals(_je.float_to_string(m_pc))
    for h in (m_v, m_fq, m_h, m_p, m_pc):
        _R.release(h)
    c.long_array_consts([10, 20, 30])
    c.invokestatic(J + "TpuColumns", "fromLongs", "([J)J")
    c.lstore(T1)
    c.long_array_consts([1, 2, 1])
    c.invokestatic(J + "TpuColumns", "fromLongs", "([J)J")
    c.lstore(T2)
    c.lload(T1)
    c.lload(T2)
    c.invokestatic(J + "Histogram", "createHistogramIfValid",
                   "(JJ)J")
    c.lstore(T3)
    c.lload(T3)
    c.double_array([0.5])
    c.invokestatic(J + "Histogram", "percentileFromHistogram",
                   "(J[D)J")
    c.lstore(T4)
    free(T1)
    free(T2)                       # inputs done; reuse T1/T2 below
    c.lload(T4)
    c.iconst(0)
    c.invokestatic(J + "TpuColumns", "getChild", "(JI)J")
    c.lstore(T2)
    c.lload(T2)
    c.invokestatic(J + "CastStrings", "fromFloat", "(J)J")
    c.lstore(T1)
    c.lload(T1)
    c.string_array(gold_pct)
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("Histogram percentile")
    free(T3)
    free(T4)
    free(T2)
    free(T1)
    c.println("surface sweep 1 ok")


    # ================= sweep part 2 =================
    # -- ParseURI remaining extractors --
    for meth, entry_args, gold in [
            ("parseProtocol", ("protocol",), None),
            ("parseQuery", ("query",), None),
            ("parsePath", ("path",), None)]:
        g = _vals(_je.parse_uri(m_uri, entry_args[0], False))
        c.lload(H_URI)
        c.iconst(0)
        c.invokestatic(J + "ParseURI", meth, "(JZ)J")
        c.lstore(T1)
        c.lload(T1)
        c.string_array(g)
        c.invokestatic(J + "TestSupport", "checkStringColumn",
                       "(J[Ljava/lang/String;)I")
        assert_check("ParseURI." + meth)
        free(T1)
    g = _vals(_je.parse_uri_query_with_key(m_uri, "a", False))
    c.lload(H_URI)
    c.ldc_string("a")
    c.iconst(0)
    c.invokestatic(J + "ParseURI", "parseQueryWithKey",
                   "(JLjava/lang/String;Z)J")
    c.lstore(T1)
    c.lload(T1)
    c.string_array(g)
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("ParseURI.parseQueryWithKey")
    free(T1)

    # -- substringIndex / NumberConverter / RegexRewriteUtils on the
    # murmur string column --
    m_str = _je.from_strings(MURMUR_IN)
    g = _vals(_je.substring_index(m_str, "a", 1))
    c.lload(H_STR)
    c.ldc_string("a")
    c.iconst(1)
    c.invokestatic(J + "GpuSubstringIndexUtils", "substringIndex",
                   "(JLjava/lang/String;I)J")
    c.lstore(T1)
    c.lload(T1)
    c.string_array(g)
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("GpuSubstringIndexUtils.substringIndex")
    free(T1)
    g = _vals(_je.number_converter_convert(m_num, 10, 16))
    c.lload(H_NUM)
    c.iconst(10)
    c.iconst(16)
    c.invokestatic(J + "NumberConverter", "convertCvCv", "(JII)J")
    c.lstore(T1)
    c.lload(T1)
    c.string_array(g)
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("NumberConverter.convertCvCv")
    free(T1)
    m_lr = _je.literal_range_pattern(m_str, "a", 1, ord("a"), ord("z"))
    g = _vals(m_lr, release=False)
    _R.release(m_lr)
    gold_bool = [1 if v else 0 for v in g]
    c.lload(H_STR)
    c.ldc_string("a")
    c.iconst(1)
    c.iconst(ord("a"))
    c.iconst(ord("z"))
    c.invokestatic(J + "RegexRewriteUtils", "literalRangePattern",
                   "(JLjava/lang/String;III)J")
    c.lstore(T1)
    c.lload(T1)
    c.int_array(gold_bool)
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("RegexRewriteUtils.literalRangePattern")
    free(T1)

    # -- GBK charset decode via the bulk string path --
    texts = ["\u4f60\u597d", "abc"]
    gbk = b"".join(t.encode("gbk") for t in texts)
    gbk_offs = [0, len(texts[0].encode("gbk")), len(gbk)]
    m_g = _je.from_strings_bulk(gbk, __import__("numpy").asarray(
        gbk_offs, "<i4").tobytes(), None)
    g = _vals(_je.charset_decode_to_utf8(m_g, "GBK", "replace"))
    _R.release(m_g)
    c.iconst(len(gbk))
    c.newarray(8)
    c.astore(REF)
    for i, b in enumerate(gbk):
        c.aload(REF)
        c.iconst(i)
        c.iconst(b if b < 128 else b - 256)
        c.bastore()
    c.aload(REF)
    c.int_array(gbk_offs)
    c.aconst_null()
    c.invokestatic(J + "TpuColumns", "fromStringsBulk", "([B[I[B)J")
    c.lstore(T1)
    c.lload(T1)
    c.ldc_string("GBK")
    c.ldc_string("replace")
    c.invokestatic(J + "CharsetDecode", "decodeToUTF8",
                   "(JLjava/lang/String;Ljava/lang/String;)J")
    c.lstore(T2)
    c.lload(T2)
    c.string_array(g)
    c.invokestatic(J + "TestSupport", "checkStringColumn",
                   "(J[Ljava/lang/String;)I")
    assert_check("CharsetDecode GBK")
    free(T1)
    free(T2)

    # -- Iceberg transforms --
    g = _vals(_je.iceberg_bucket(m_longs, 16))
    c.lload(H_LONGS)
    c.iconst(16)
    c.invokestatic(J + "IcebergBucket", "bucket", "(JI)J")
    c.lstore(T1)
    c.lload(T1)
    c.int_array(g)
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("IcebergBucket.bucket")
    free(T1)
    g = _vals(_je.iceberg_truncate(m_longs, 10))
    c.lload(H_LONGS)
    c.iconst(10)
    c.invokestatic(J + "IcebergTruncate", "truncate", "(JI)J")
    c.lstore(T1)
    c.lload(T1)
    c.long_array_consts(g)
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("IcebergTruncate.truncate")
    free(T1)

    # -- ZOrder --
    m_i1 = _je.from_ints([1, 2])
    m_i2 = _je.from_ints([3, 1])
    g_h = _vals(_je.hilbert_index(4, [m_i1, m_i2]))
    c.int_array([1, 2])
    c.invokestatic(J + "TpuColumns", "fromInts", "([I)J")
    c.lstore(T1)
    c.int_array([3, 1])
    c.invokestatic(J + "TpuColumns", "fromInts", "([I)J")
    c.lstore(T2)
    c.iconst(4)
    c.long_array_locals([T1, T2])
    c.invokestatic(J + "ZOrder", "hilbertIndex", "(I[J)J")
    c.lstore(T3)
    c.lload(T3)
    c.long_array_consts(g_h)
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("ZOrder.hilbertIndex")
    free(T3)
    m_z = _je.interleave_bits([m_i1, m_i2])
    g_z = _vals(m_z)
    z_offs = [0]
    z_vals = []
    for row in g_z:
        z_vals.extend(int(b) for b in row)
        z_offs.append(len(z_vals))
    c.long_array_locals([T1, T2])
    c.invokestatic(J + "ZOrder", "interleaveBits", "([J)J")
    c.lstore(T3)
    c.int_array(z_offs)
    c.long_array_consts(z_vals)
    c.invokestatic(J + "TestSupport", "makeListOfInts", "([I[J)J")
    c.lstore(67)
    c.lload(T3)
    c.lload(67)
    c.invokestatic(J + "TestSupport", "checkColumnsEqual", "(JJ)I")
    assert_check("ZOrder.interleaveBits golden")
    free(67)
    _R.release(m_i1)
    _R.release(m_i2)
    free(T1)
    free(T2)
    free(T3)

    # -- Aggregation64Utils --
    m_lo = _je.extract_chunk32_from_64bit(m_longs, "int64", 0)
    m_hi = _je.extract_chunk32_from_64bit(m_longs, "int64", 1)
    g_lo = _vals(m_lo, release=False)
    asm = _je.assemble64_from_sum(m_lo, m_hi, "int64")
    g_asm = _vals(asm[0] if isinstance(asm, (list, tuple)) else asm)
    c.lload(H_LONGS)
    c.ldc_string("int64")
    c.iconst(0)
    c.invokestatic(J + "Aggregation64Utils", "extractChunk32From64bit",
                   "(JLjava/lang/String;I)J")
    c.lstore(T1)
    c.lload(T1)
    c.int_array(g_lo)
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("Aggregation64Utils.extractChunk32From64bit")
    c.lload(H_LONGS)
    c.ldc_string("int64")
    c.iconst(1)
    c.invokestatic(J + "Aggregation64Utils", "extractChunk32From64bit",
                   "(JLjava/lang/String;I)J")
    c.lstore(T2)
    c.lload(T1)
    c.lload(T2)
    c.ldc_string("int64")
    c.invokestatic(J + "Aggregation64Utils", "assemble64FromSum",
                   "(JJLjava/lang/String;)[J")
    c.astore(REF)
    c.aload(REF)
    c.iconst(0)
    c.laload()
    c.lstore(T3)
    c.lload(T3)
    c.long_array_consts(g_asm)
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("Aggregation64Utils.assemble64FromSum")
    # free every element the native returned (mirror knows the count)
    n_asm = len(asm) if isinstance(asm, (list, tuple)) else 1
    for k in range(1, n_asm):
        c.aload(REF)
        c.iconst(k)
        c.laload()
        c.invokestatic(J + "TpuColumns", "free", "(J)V")
    for h in (m_lo, m_hi):
        _R.release(h)
    if isinstance(asm, (list, tuple)):
        for h in asm[1:]:
            _R.release(h)
    free(T1)
    free(T2)
    free(T3)
    c.println("surface sweep 2 ok")

    # ================= sweep part 3 =================
    # -- BloomFilter merge/serialize/deserialize (on live BF, BF2) --
    c.long_array_locals([BF, BF2])
    c.invokestatic(J + "BloomFilter", "merge", "([J)J")
    c.lstore(T1)
    c.lload(T1)
    c.invokestatic(J + "BloomFilter", "serialize", "(J)[B")
    c.astore(REF)
    c.aload(REF)
    c.invokestatic(J + "BloomFilter", "deserialize", "([B)J")
    c.lstore(T2)
    c.lload(T2)
    c.lload(H_LONGS)
    c.invokestatic(J + "BloomFilter", "probe", "(JJ)J")
    c.lstore(T3)
    c.lload(T3)
    c.int_array([1, 1, 1])
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("BloomFilter merge/serialize/deserialize/probe")
    free(T1)
    free(T2)
    free(T3)

    # -- listSlice scalar/column operand variants --
    LSLC, LSST, LSLN = 72, 74, 76   # reuse T-slots as named inputs
    m_lst = _je.make_list_of_ints([0, 3, 5], [1, 2, 3, 4, 5])
    m_st = _je.from_ints([1, 2])
    m_ln = _je.from_ints([2, 1])
    c.int_array([0, 3, 5])
    c.long_array_consts([1, 2, 3, 4, 5])
    c.invokestatic(J + "TestSupport", "makeListOfInts", "([I[J)J")
    c.lstore(LSLC)
    c.int_array([1, 2])
    c.invokestatic(J + "TpuColumns", "fromInts", "([I)J")
    c.lstore(LSST)
    c.int_array([2, 1])
    c.invokestatic(J + "TpuColumns", "fromInts", "([I)J")
    c.lstore(LSLN)
    combos = [
        ("listSliceSC", "(JIJZ)J", 1, "COL"),
        ("listSliceCS", "(JJIZ)J", "COL", 1),
        ("listSliceCC", "(JJJZ)J", "COL", "COL"),
    ]
    for meth, desc, a_st, a_ln in combos:
        start_is_col = a_st == "COL"
        len_is_col = a_ln == "COL"
        g_h = _je.list_slice(m_lst, m_st if start_is_col else a_st,
                             m_ln if len_is_col else a_ln,
                             start_is_col, len_is_col, True)
        gl = _vals(g_h, release=False)
        exp_offs = [0]
        exp_vals = []
        for row in gl:
            exp_vals.extend(row if row is not None else [])
            exp_offs.append(len(exp_vals))
        _R.release(g_h)
        c.lload(LSLC)
        if start_is_col:
            c.lload(LSST)
        else:
            c.iconst(a_st)
        if len_is_col:
            c.lload(LSLN)
        else:
            c.iconst(a_ln)
        c.iconst(1)
        c.invokestatic(J + "GpuListSliceUtils", meth, desc)
        c.lstore(78)
        c.int_array(exp_offs)
        c.long_array_consts(exp_vals)
        c.invokestatic(J + "TestSupport", "makeListOfInts", "([I[J)J")
        c.lstore(67)               # 67-68 dead since the kudo block
        c.lload(78)
        c.lload(67)
        c.invokestatic(J + "TestSupport", "checkColumnsEqual",
                       "(JJ)I")
        assert_check("GpuListSliceUtils." + meth)
        free(78)
        free(67)

    for h in (m_lst, m_st, m_ln):
        _R.release(h)
    free(LSLC)
    free(LSST)
    free(LSLN)

    # -- MapUtils / GpuMapZipWithUtils --
    m_map = _je.make_map_column([0, 2, 3], ["a", "b", "c"],
                                ["1", "2", "3"])
    assert _je.map_is_valid(m_map, False)
    c.int_array([0, 2, 3])
    c.string_array(["a", "b", "c"])
    c.string_array(["1", "2", "3"])
    c.invokestatic(J + "TestSupport", "makeMapColumn",
                   "([I[Ljava/lang/String;[Ljava/lang/String;)J")
    c.lstore(T1)
    c.lload(T1)
    c.iconst(0)
    c.invokestatic(J + "MapUtils", "isValidMap", "(JZ)Z")
    assert_check("MapUtils.isValidMap")
    c.lload(T1)
    c.iconst(1)
    c.invokestatic(J + "MapUtils", "mapFromEntries", "(JZ)J")
    c.lstore(T2)
    c.lload(T1)
    c.lload(T1)
    c.invokestatic(J + "GpuMapZipWithUtils", "mapZip", "(JJ)J")
    c.lstore(T3)
    c.lload(T1)
    c.iconst(0)
    c.invokestatic(J + "Map", "sortMapColumn", "(JZ)J")
    c.lstore(T4)
    free(T1)
    free(T2)
    free(T3)
    free(T4)
    _R.release(m_map)

    # -- Protobuf.decodeToStruct + getChild --
    pmsgs = ["\x08\x05", "\x08\x2a"]
    m_pb = _je.from_strings(pmsgs)
    m_ps = _je.protobuf_decode_to_struct(
        m_pb, [1], ["int64"], [0], [False])
    m_pc = _je.struct_child(m_ps, 0)
    g_pb = _vals(m_pc, release=False)
    for h in (m_pb, m_ps, m_pc):
        _R.release(h)
    c.string_array(pmsgs)
    c.invokestatic(J + "TpuColumns", "fromStrings",
                   "([Ljava/lang/String;)J")
    c.lstore(T1)
    c.lload(T1)
    c.int_array([1])
    c.string_array(["int64"])
    c.int_array([0])
    c.iconst(1)
    c.newarray(4)                  # boolean[1]{false}
    c.invokestatic(J + "Protobuf", "decodeToStruct",
                   "(J[I[Ljava/lang/String;[I[Z)J")
    c.lstore(T2)
    c.lload(T2)
    c.iconst(0)
    c.invokestatic(J + "TpuColumns", "getChild", "(JI)J")
    c.lstore(T3)
    c.lload(T3)
    c.long_array_consts(g_pb)
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("Protobuf.decodeToStruct")
    free(T1)
    free(T2)
    free(T3)

    # -- DecimalUtils add/subtract/divide on live H_DA/H_DB --
    m_da = _je.from_decimals([125, 250], -2, "decimal128")
    m_db = _je.from_decimals([200, 400], -2, "decimal128")
    for meth, scale in (("add128", -2), ("subtract128", -2),
                        ("divide128", -6)):
        pyname = {"add128": "add", "subtract128": "sub",
                  "divide128": "divide"}[meth]
        res = _je.decimal128_binop(pyname, m_da, m_db, scale)
        g_flags = _vals(res[0], release=False)
        g_res = _vals(res[1], release=False)
        for h in res:
            _R.release(h)
        c.lload(H_DA)
        c.lload(H_DB)
        c.iconst(scale)
        c.invokestatic(J + "DecimalUtils", meth, "(JJI)[J")
        c.astore(REF)
        c.aload(REF)
        c.iconst(1)
        c.laload()
        c.lstore(T1)
        c.lload(T1)
        c.long_array_consts(g_res)   # unscaled ints (to_pylist)
        c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
        assert_check("DecimalUtils." + meth)
        c.aload(REF)
        c.iconst(0)
        c.laload()
        c.lstore(67)
        c.lload(67)
        c.int_array([1 if f else 0 for f in g_flags])
        c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
        assert_check("DecimalUtils." + meth + " overflow flags")
        free(67)
        free(T1)
    for h in (m_da, m_db):
        _R.release(h)
    c.println("surface sweep 3 ok")

    # ================= sweep part 4 =================
    # -- typed timestamp column via convertFromRows, then the
    # datetime natives (rebase both ways, truncate, tz convert) --
    micros = [1577836800000000, 946684800000000]   # 2020/2000 UTC
    m_tsrc = _je.from_longs(micros)
    m_rows = _je.convert_to_rows([m_tsrc])
    ts_handles = _je.convert_from_rows(m_rows, ["timestamp_micros"],
                                       [0])
    m_ts = ts_handles[0]
    m_j = _je.datetime_rebase(m_ts, True)
    g_j = _vals(m_j, release=False)
    g_back = _vals(_je.datetime_rebase(m_j, False))
    _R.release(m_j)
    g_trunc = _vals(_je.datetime_truncate(m_ts, "month"))
    g_tz = _vals(_je.timezone_convert(m_ts, "America/Los_Angeles",
                                      False))
    m_tz = _je.timezone_convert(m_ts, "America/Los_Angeles", False)
    g_tz_back = _vals(_je.timezone_convert(m_tz,
                                           "America/Los_Angeles",
                                           True))
    g_year = _vals(_je.iceberg_datetime(m_ts, "year"))
    _R.release(m_tz)
    _R.release(m_ts)
    _R.release(m_rows)
    _R.release(m_tsrc)

    c.long_array_consts(micros)
    c.invokestatic(J + "TpuColumns", "fromLongs", "([J)J")
    c.lstore(T1)
    c.long_array_locals([T1])
    c.invokestatic(J + "RowConversion", "convertToRows", "([J)J")
    c.lstore(T2)
    c.lload(T2)
    c.string_array(["timestamp_micros"])
    c.int_array([0])
    c.invokestatic(J + "RowConversion", "convertFromRows",
                   "(J[Ljava/lang/String;[I)[J")
    c.astore(REF)
    c.aload(REF)
    c.iconst(0)
    c.laload()
    c.lstore(T3)                   # typed timestamp column
    c.lload(T3)
    c.invokestatic(J + "DateTimeRebase", "rebaseGregorianToJulian",
                   "(J)J")
    c.lstore(T4)
    c.lload(T4)
    c.long_array_consts(g_j)
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("DateTimeRebase.rebaseGregorianToJulian")
    c.lload(T4)
    c.invokestatic(J + "DateTimeRebase", "rebaseJulianToGregorian",
                   "(J)J")
    c.lstore(67)
    c.lload(67)
    c.long_array_consts(g_back)
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("DateTimeRebase.rebaseJulianToGregorian")
    free(67)
    free(T4)
    c.lload(T3)
    c.ldc_string("month")
    c.invokestatic(J + "DateTimeUtils", "truncate",
                   "(JLjava/lang/String;)J")
    c.lstore(T4)
    c.lload(T4)
    c.long_array_consts(g_trunc)
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("DateTimeUtils.truncate")
    free(T4)
    c.lload(T3)
    c.ldc_string("America/Los_Angeles")
    c.invokestatic(J + "GpuTimeZoneDB",
                   "convertUTCTimestampToTimeZone",
                   "(JLjava/lang/String;)J")
    c.lstore(T4)
    c.lload(T4)
    c.long_array_consts(g_tz)
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("GpuTimeZoneDB.convertUTCTimestampToTimeZone")
    c.lload(T4)
    c.ldc_string("America/Los_Angeles")
    c.invokestatic(J + "GpuTimeZoneDB", "convertTimestampToUTC",
                   "(JLjava/lang/String;)J")
    c.lstore(67)
    c.lload(67)
    c.long_array_consts(g_tz_back)
    c.invokestatic(J + "TestSupport", "checkLongColumn", "(J[J)I")
    assert_check("GpuTimeZoneDB.convertTimestampToUTC")
    free(67)
    free(T4)

    # -- IcebergDateTimeUtil.transform on the typed timestamp --
    c.lload(T3)
    c.ldc_string("year")
    c.invokestatic(J + "IcebergDateTimeUtil", "transform",
                   "(JLjava/lang/String;)J")
    c.lstore(T4)
    c.lload(T4)
    c.int_array(g_year)
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("IcebergDateTimeUtil.transform(year)")
    free(T4)
    free(T1)
    free(T2)
    free(T3)

    # -- Version / registry / priority / host-table scalars --
    assert _je.version_is_vanilla_320(0, 3, 2, 0)
    c.iconst(0)
    c.iconst(3)
    c.iconst(2)
    c.iconst(0)
    c.invokestatic(J + "Version", "isVanilla320", "(IIII)Z")
    assert_check("Version.isVanilla320(0,3,2,0)")
    c.lconst(424242)
    c.invokestatic(J + "ThreadStateRegistry", "addThread", "(J)V")
    c.invokestatic(J + "ThreadStateRegistry", "knownThreads", "()[J")
    c.arraylength()
    assert_check("ThreadStateRegistry.knownThreads non-empty")
    c.lconst(424242)
    c.invokestatic(J + "ThreadStateRegistry", "removeThread", "(J)V")
    g_pri = _je.task_priority_get(7)
    ok_pri = Label()
    c.lconst(7)
    c.invokestatic(J + "TaskPriority", "getTaskPriority", "(J)J")
    c.lconst(g_pri)
    c.lcmp()
    c.ifeq_lbl(ok_pri)
    c.iconst(0)
    c.ldc_string("TaskPriority.getTaskPriority mismatch")
    c.invokestatic(J + "TestSupport", "assertTrue",
                   "(ILjava/lang/String;)V")
    c.place(ok_pri)
    c.lconst(7)
    c.invokestatic(J + "TaskPriority", "taskDone", "(J)V")
    # hostTableNumRows on a fresh host table
    ok_rows = Label()
    c.long_array_locals([H_LONGS])
    c.invokestatic(J + "KudoSerializer", "hostTableFromColumns",
                   "([J)J")
    c.lstore(T1)
    c.lload(T1)
    c.invokestatic(J + "KudoSerializer", "hostTableNumRows", "(J)J")
    c.lconst(3)
    c.lcmp()
    c.ifeq_lbl(ok_rows)
    c.iconst(0)
    c.ldc_string("hostTableNumRows != 3")
    c.invokestatic(J + "TestSupport", "assertTrue",
                   "(ILjava/lang/String;)V")
    c.place(ok_rows)
    c.lload(T1)
    c.invokestatic(J + "KudoSerializer", "freeHostTable", "(J)V")
    # HostTable.sizeBytes > 0
    ok_sz = Label()
    c.long_array_locals([H_LONGS])
    c.invokestatic(J + "HostTable", "fromTable", "([J)J")
    c.lstore(T1)
    c.lload(T1)
    c.invokestatic(J + "HostTable", "sizeBytes", "(J)J")
    c.lconst(0)
    c.lcmp()
    c.iconst(1)
    c.if_icmp("eq", ok_sz)
    c.iconst(0)
    c.ldc_string("HostTable.sizeBytes not positive")
    c.invokestatic(J + "TestSupport", "assertTrue",
                   "(ILjava/lang/String;)V")
    c.place(ok_sz)
    c.lload(T1)
    c.invokestatic(J + "HostTable", "free", "(J)V")


    # -- CaseWhen.selectFirstTrueIndex over BOOL8 columns (produced
    # by literalRangePattern) --
    m_b1 = _je.literal_range_pattern(m_str, "a", 1, ord("a"),
                                     ord("z"))
    m_b2 = _je.literal_range_pattern(m_str, "z", 1, ord("a"),
                                     ord("z"))
    g_cw = _vals(_je.select_first_true_index([m_b1, m_b2]))
    _R.release(m_b1)
    _R.release(m_b2)
    c.lload(H_STR)
    c.ldc_string("a")
    c.iconst(1)
    c.iconst(ord("a"))
    c.iconst(ord("z"))
    c.invokestatic(J + "RegexRewriteUtils", "literalRangePattern",
                   "(JLjava/lang/String;III)J")
    c.lstore(T1)
    c.lload(H_STR)
    c.ldc_string("z")
    c.iconst(1)
    c.iconst(ord("a"))
    c.iconst(ord("z"))
    c.invokestatic(J + "RegexRewriteUtils", "literalRangePattern",
                   "(JLjava/lang/String;III)J")
    c.lstore(T2)
    c.long_array_locals([T1, T2])
    c.invokestatic(J + "CaseWhen", "selectFirstTrueIndex", "([J)J")
    c.lstore(T3)
    c.lload(T3)
    c.int_array(g_cw)
    c.invokestatic(J + "TestSupport", "checkIntColumn", "(J[I)I")
    assert_check("CaseWhen.selectFirstTrueIndex")
    free(T1)
    free(T2)
    free(T3)
    # -- telemetry + timezone enumeration --
    c.iconst(0)
    c.invokestatic(J + "nvml/NVML", "getSnapshotPacked", "(I)[J")
    c.arraylength()
    c.iconst(7)
    c.idiv()
    assert_check("NVML.getSnapshotPacked 7 slots")
    c.iconst(0)
    c.invokestatic(J + "nvml/NVML", "getDeviceName",
                   "(I)Ljava/lang/String;")
    c.invokevirtual("java/lang/String", "length", "()I")
    assert_check("NVML.getDeviceName non-empty")
    c.invokestatic(J + "OrcDstRuleExtractor", "timezoneIds",
                   "()[Ljava/lang/String;")
    c.arraylength()
    assert_check("OrcDstRuleExtractor.timezoneIds non-empty")
    # JSON path variants: wildcard + array index through JNI
    m_jv = _je.from_strings(['{"a": [1, 2, 3]}', '{"a": []}'])
    g_w = _vals(_je.get_json_object(m_jv, "$.a[*]"))
    g_i = _vals(_je.get_json_object(m_jv, "$.a[1]"))
    _R.release(m_jv)
    c.string_array(['{"a": [1, 2, 3]}', '{"a": []}'])
    c.invokestatic(J + "TpuColumns", "fromStrings",
                   "([Ljava/lang/String;)J")
    c.lstore(T1)
    for path, gold in (("$.a[*]", g_w), ("$.a[1]", g_i)):
        c.lload(T1)
        c.ldc_string(path)
        c.invokestatic(J + "JSONUtils", "getJsonObject",
                       "(JLjava/lang/String;)J")
        c.lstore(T2)
        c.lload(T2)
        c.string_array(gold)
        c.invokestatic(J + "TestSupport", "checkStringColumn",
                       "(J[Ljava/lang/String;)I")
        assert_check("getJsonObject " + path)
        free(T2)
    free(T1)

    # -- multi-device SPMD query driven from the JVM ---------------
    # (4 virtual CPU devices via SPARK_RAPIDS_TPU_CPU_DEVICES; the
    # oracle runs at emission time over the same seeded data)
    from spark_rapids_tpu.models import tpcds as _tp
    _d5 = _tp.q5_mesh_data(256, 6, 4)   # SAME prep the entry runs
    _q5_gold = []
    for row in _tp.oracle_q5(_d5, 6):
        _q5_gold.extend(int(x) for x in row)
    c.iconst(4)
    c.iconst(256)
    c.iconst(6)
    c.invokestatic(J + "TpuRuntime", "runDistributedQ5", "(III)[J")
    c.astore(REF)
    jl_ok = Label()
    c.aload(REF)
    c.arraylength()
    c.iconst(len(_q5_gold))
    c.if_icmp("eq", jl_ok)
    c.iconst(0)
    c.ldc_string("distributed q5 row count mismatch")
    c.invokestatic(J + "TestSupport", "assertTrue",
                   "(ILjava/lang/String;)V")
    c.place(jl_ok)
    for _k, _v in enumerate(_q5_gold):
        ok_k = Label()
        c.aload(REF)
        c.iconst(_k)
        c.laload()
        c.lconst(_v)
        c.lcmp()
        c.ifeq_lbl(ok_k)
        c.iconst(0)
        c.ldc_string("distributed q5 value mismatch @%d" % _k)
        c.invokestatic(J + "TestSupport", "assertTrue",
                       "(ILjava/lang/String;)V")
        c.place(ok_k)
    c.println("distributed q5 from the JVM ok (%d values)"
              % len(_q5_gold))

    # -- and the q72 fact-fact join chain on the same mesh --
    _d72 = _tp.q72_mesh_data(192, 12, 4)
    _q72_gold = []
    for row in _tp.oracle_q72(_d72, 12, 16, week0=11_000 // 7):
        _q72_gold.extend(int(x) for x in row)
    c.iconst(4)
    c.iconst(192)
    c.iconst(12)
    c.invokestatic(J + "TpuRuntime", "runDistributedQ72", "(III)[J")
    c.astore(REF)
    j72_ok = Label()
    c.aload(REF)
    c.arraylength()
    c.iconst(len(_q72_gold))
    c.if_icmp("eq", j72_ok)
    c.iconst(0)
    c.ldc_string("distributed q72 row count mismatch")
    c.invokestatic(J + "TestSupport", "assertTrue",
                   "(ILjava/lang/String;)V")
    c.place(j72_ok)
    for _k, _v in enumerate(_q72_gold):
        ok_k = Label()
        c.aload(REF)
        c.iconst(_k)
        c.laload()
        c.lconst(_v)
        c.lcmp()
        c.ifeq_lbl(ok_k)
        c.iconst(0)
        c.ldc_string("distributed q72 value mismatch @%d" % _k)
        c.invokestatic(J + "TestSupport", "assertTrue",
                       "(ILjava/lang/String;)V")
        c.place(ok_k)
    c.println("distributed q72 from the JVM ok (%d values)"
              % len(_q72_gold))
    c.println("surface sweep 4 ok")

    _R.release(m_str)
    for h in (m_longs, m_num, m_uri):
        _R.release(h)


def build_kudo_bench(outdir: str):
    """KudoBench: the multi-threaded JVM shuffle-write bench over the
    GIL-free native kudo path (VERDICT r4 #1 'done' criterion: the
    Python route cannot scale past 1 thread; this one must).

    Emits KudoBenchWorker (extends Thread; mode 0 = writeHostTable
    loop, mode 1 = mergeToHostTable+free loop — neither ever enters
    the embedded interpreter) and KudoBench.main, which builds a
    ~260k-row [int64, uuid-string] table, exports it once, then times
    the SAME total number of partition writes split across 1/2/4/8
    threads, a post-thread ordering-pin write, the SAME total number
    of blob merges split across 1/8 threads, and the 10MB bulk string
    crossing.  Output lines:
      kudo_bench bytes_per_write: <n>
      kudo_bench threads=<t> writes=<n> wall_ns: <ns>
      post_thread_write bytes: <n>
      kudo_merge threads=<t> merges=<n> wall_ns: <ns>
      bulk_ingest_10MB wall_ns: <ns> / bulk_readback_10MB wall_ns: <ns>
    """
    J = f"{PKG}/"
    WORKER = f"{PKG}/KudoBenchWorker"

    # ---- worker: extends Thread, public fields, run() loop ----------
    cf = ClassFile(WORKER, super_name="java/lang/Thread", final=False,
                   major=49)
    for fname, fdesc in (("table", "J"), ("off", "I"), ("cnt", "I"),
                         ("iters", "I")):
        cf.add_field(fname, fdesc)
    c = Code(cf.cp, max_locals=1)
    c.aload(0)
    c.invokespecial("java/lang/Thread", "<init>", "()V")
    c.return_void()
    cf.add_code_method("<init>", "()V", c, flags=ACC_PUBLIC)
    cf.add_field("blob", "[B")
    cf.add_field("mode", "I")
    c = Code(cf.cp, max_locals=2)
    loop, done, merge_body, step_done = (Label(), Label(), Label(),
                                         Label())
    c.iconst(0)
    c.istore(1)
    c.place(loop)
    c.iload(1)
    c.aload(0)
    c.getfield(WORKER, "iters", "I")
    c.if_icmp("ge", done)
    c.aload(0)
    c.getfield(WORKER, "mode", "I")
    c.iconst(1)
    c.if_icmp("eq", merge_body)
    # mode 0: partition write
    c.aload(0)
    c.getfield(WORKER, "table", "J")
    c.aload(0)
    c.getfield(WORKER, "off", "I")
    c.aload(0)
    c.getfield(WORKER, "cnt", "I")
    c.invokestatic(J + "KudoSerializer", "writeHostTable", "(JII)[B")
    c.pop_op()
    c.goto(step_done)
    # mode 1: merge the shared blob into a host table, free it
    c.place(merge_body)
    c.aload(0)
    c.getfield(WORKER, "blob", "[B")
    c.aload(0)
    c.getfield(WORKER, "table", "J")
    c.invokestatic(J + "KudoSerializer", "mergeToHostTable", "([BJ)J")
    c.invokestatic(J + "KudoSerializer", "freeHostTable", "(J)V")
    c.place(step_done)
    c.iinc(1, 1)
    c.goto(loop)
    c.place(done)
    c.return_void()
    c.max_stack = max(c.max_stack, 6)
    cf.add_code_method("run", "()V", c, flags=ACC_PUBLIC)
    path = os.path.join(outdir, PKG, "KudoBenchWorker.class")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(cf.serialize())

    # ---- driver -----------------------------------------------------
    N = 262144          # rows
    PART = 16384        # rows per partition write
    TOTAL = 512         # total writes per thread config
    cf = ClassFile(f"{PKG}/KudoBench", major=49)
    c = Code(cf.cp, max_locals=64)
    ARR, I, HL, HS, HT, TSTART, TEND = 2, 3, 4, 6, 8, 10, 12
    WBASE = 20          # workers live in locals 20..27
    c.aload(0)
    c.iconst(0)
    c.aaload()
    c.invokestatic("java/lang/System", "load", "(Ljava/lang/String;)V")
    c.invokestatic(J + "TpuRuntime", "initialize", "()V")
    # long[] of N sequential values
    c.iconst(N)
    c.newarray(T_LONG)
    c.astore(ARR)
    c.iconst(0)
    c.istore(I)
    loop, done = Label(), Label()
    c.place(loop)
    c.iload(I)
    c.iconst(N)
    c.if_icmp("ge", done)
    c.aload(ARR)
    c.iload(I)
    c.iload(I)
    c.i2l()
    c.lastore()
    c.iinc(I, 1)
    c.goto(loop)
    c.place(done)
    c.aload(ARR)
    c.invokestatic(J + "TpuColumns", "fromLongs", "([J)J")
    c.lstore(HL)
    c.iconst(N)
    c.lconst(12345)
    c.invokestatic(J + "StringUtils", "randomUUIDs", "(IJ)J")
    c.lstore(HS)
    c.long_array_locals([HL, HS])
    c.invokestatic(J + "KudoSerializer", "hostTableFromColumns",
                   "([J)J")
    c.lstore(HT)
    # bytes per write (for external MB/s computation)
    c.println("kudo_bench bytes_per_write:")
    c.getstatic("java/lang/System", "out", "Ljava/io/PrintStream;")
    c.lload(HT)
    c.iconst(0)
    c.iconst(PART)
    c.invokestatic(J + "KudoSerializer", "writeHostTable", "(JII)[B")
    c.arraylength()
    c.invokevirtual("java/io/PrintStream", "println", "(I)V")
    for nthreads in (1, 2, 4, 8):
        iters = TOTAL // nthreads
        for w in range(nthreads):
            c.new_obj(WORKER)
            c.dup()
            c.invokespecial(WORKER, "<init>", "()V")
            c.dup()
            c.lload(HT)
            c.putfield(WORKER, "table", "J")
            c.dup()
            c.iconst((w * PART) % N)
            c.putfield(WORKER, "off", "I")
            c.dup()
            c.iconst(PART)
            c.putfield(WORKER, "cnt", "I")
            c.dup()
            c.iconst(iters)
            c.putfield(WORKER, "iters", "I")
            c.astore(WBASE + w)
        c.invokestatic("java/lang/System", "nanoTime", "()J")
        c.lstore(TSTART)
        for w in range(nthreads):
            c.aload(WBASE + w)
            c.invokevirtual("java/lang/Thread", "start", "()V")
        for w in range(nthreads):
            c.aload(WBASE + w)
            c.invokevirtual("java/lang/Thread", "join", "()V")
        c.invokestatic("java/lang/System", "nanoTime", "()J")
        c.lstore(TEND)
        c.println(f"kudo_bench threads={nthreads} writes={TOTAL} "
                  "wall_ns:")
        c.getstatic("java/lang/System", "out", "Ljava/io/PrintStream;")
        c.lload(TEND)
        c.lload(TSTART)
        c.lsub()
        c.invokevirtual("java/io/PrintStream", "println", "(J)V")
    # --- post-thread-config write: ordering pin.  Every section
    # below MUST run before the handle cleanup at the end of main — a
    # section pasted after the frees once produced a baffling
    # use-after-free hunt (the "rogue free" was this bench's own
    # freeHostTable) ------------------------------------------------
    BLOB = 28
    c.println("post_thread_write bytes:")
    c.getstatic("java/lang/System", "out", "Ljava/io/PrintStream;")
    c.lload(HT)
    c.iconst(0)
    c.iconst(PART)
    c.invokestatic(J + "KudoSerializer", "writeHostTable", "(JII)[B")
    c.arraylength()
    c.invokevirtual("java/io/PrintStream", "println", "(I)V")

    # --- merge scaling: same blob merged by 1 vs 8 threads ----------
    MERGES = 64
    c.lload(HT)
    c.iconst(0)
    c.iconst(N // 2)
    c.invokestatic(J + "KudoSerializer", "writeHostTable", "(JII)[B")
    c.astore(BLOB)
    for nthreads in (1, 8):
        m_iters = MERGES // nthreads
        for w in range(nthreads):
            c.new_obj(WORKER)
            c.dup()
            c.invokespecial(WORKER, "<init>", "()V")
            c.dup()
            c.iconst(1)
            c.putfield(WORKER, "mode", "I")
            c.dup()
            c.aload(BLOB)
            c.putfield(WORKER, "blob", "[B")
            c.dup()
            c.lload(HT)
            c.putfield(WORKER, "table", "J")
            c.dup()
            c.iconst(m_iters)
            c.putfield(WORKER, "iters", "I")
            c.astore(WBASE + w)
        c.invokestatic("java/lang/System", "nanoTime", "()J")
        c.lstore(TSTART)
        for w in range(nthreads):
            c.aload(WBASE + w)
            c.invokevirtual("java/lang/Thread", "start", "()V")
        for w in range(nthreads):
            c.aload(WBASE + w)
            c.invokevirtual("java/lang/Thread", "join", "()V")
        c.invokestatic("java/lang/System", "nanoTime", "()J")
        c.lstore(TEND)
        c.println(f"kudo_merge threads={nthreads} merges={MERGES} "
                  "wall_ns:")
        c.getstatic("java/lang/System", "out", "Ljava/io/PrintStream;")
        c.lload(TEND)
        c.lload(TSTART)
        c.lsub()
        c.invokevirtual("java/io/PrintStream", "println", "(J)V")

    c.lload(HT)
    c.invokestatic(J + "KudoSerializer", "freeHostTable", "(J)V")
    c.lload(HL)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.lload(HS)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")

    # --- bulk string JNI path: MB/s for a 10MB single-crossing
    # ingest and readback (VERDICT r4 weak #4 'done' criterion) ----
    BCH, BOF, BH, I2 = 30, 31, 32, 34   # 32-33 long, 34 int
    _emit_bulk_string_arrays(c, BCH, BOF, I2, 98)
    # warm once, then timed ingest + readback
    c.aload(BCH)
    c.aload(BOF)
    c.aconst_null()
    c.invokestatic(J + "TpuColumns", "fromStringsBulk", "([B[I[B)J")
    c.invokestatic(J + "TpuColumns", "free", "(J)V")
    c.invokestatic("java/lang/System", "nanoTime", "()J")
    c.lstore(TSTART)
    c.aload(BCH)
    c.aload(BOF)
    c.aconst_null()
    c.invokestatic(J + "TpuColumns", "fromStringsBulk", "([B[I[B)J")
    c.lstore(BH)
    c.invokestatic("java/lang/System", "nanoTime", "()J")
    c.lstore(TEND)
    c.println("bulk_ingest_10MB wall_ns:")
    c.getstatic("java/lang/System", "out", "Ljava/io/PrintStream;")
    c.lload(TEND)
    c.lload(TSTART)
    c.lsub()
    c.invokevirtual("java/io/PrintStream", "println", "(J)V")
    c.invokestatic("java/lang/System", "nanoTime", "()J")
    c.lstore(TSTART)
    c.lload(BH)
    c.invokestatic(J + "TpuColumns", "getStringChars", "(J)[B")
    c.pop_op()
    c.invokestatic("java/lang/System", "nanoTime", "()J")
    c.lstore(TEND)
    c.println("bulk_readback_10MB wall_ns:")
    c.getstatic("java/lang/System", "out", "Ljava/io/PrintStream;")
    c.lload(TEND)
    c.lload(TSTART)
    c.lsub()
    c.invokevirtual("java/io/PrintStream", "println", "(J)V")
    c.lload(BH)
    c.invokestatic(J + "TpuColumns", "free", "(J)V")

    c.invokestatic(J + "TpuRuntime", "shutdown", "()V")
    c.println("kudo bench done")
    c.return_void()
    c.max_stack = max(c.max_stack, 10)
    cf.add_code_method("main", "([Ljava/lang/String;)V", c)
    path = os.path.join(outdir, PKG, "KudoBench.class")
    with open(path, "wb") as f:
        f.write(cf.serialize())


def main():
    outdir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "java", "classes")
    build_natives(outdir)
    build_exceptions(outdir)
    build_smoke_test(outdir, _computed_goldens())
    build_oom_smoke_test(outdir)
    build_bufn_smoke_test(outdir)
    build_cudf_classes(outdir)
    build_kudo_bench(outdir)
    print(f"emitted classes under {outdir}")


if __name__ == "__main__":
    main()
