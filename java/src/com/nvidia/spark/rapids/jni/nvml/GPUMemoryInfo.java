package com.nvidia.spark.rapids.jni.nvml;

/**
 * Device MemoryInfo snapshot (reference nvml/GPUMemoryInfo.java;
 * TPU source: utils/telemetry.py — accelerator metrics where the
 * runtime exposes them, host-derived fallbacks where it does not).
 */
public final class GPUMemoryInfo {
  public final long totalBytes;
  public final long usedBytes;
  public final long freeBytes;

  public GPUMemoryInfo(long totalBytes, long usedBytes, long freeBytes) {
    this.totalBytes = totalBytes;
    this.usedBytes = usedBytes;
    this.freeBytes = freeBytes;
  }
}
