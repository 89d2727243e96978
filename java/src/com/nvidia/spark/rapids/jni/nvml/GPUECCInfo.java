package com.nvidia.spark.rapids.jni.nvml;

/**
 * Device ECCInfo snapshot (reference nvml/GPUECCInfo.java;
 * TPU source: utils/telemetry.py — accelerator metrics where the
 * runtime exposes them, host-derived fallbacks where it does not).
 */
public final class GPUECCInfo {
  public final long correctedErrors;
  public final long uncorrectedErrors;

  public GPUECCInfo(long correctedErrors, long uncorrectedErrors) {
    this.correctedErrors = correctedErrors;
    this.uncorrectedErrors = uncorrectedErrors;
  }
}
