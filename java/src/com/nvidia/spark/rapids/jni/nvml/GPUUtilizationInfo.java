package com.nvidia.spark.rapids.jni.nvml;

/**
 * Device UtilizationInfo snapshot (reference nvml/GPUUtilizationInfo.java;
 * TPU source: utils/telemetry.py — accelerator metrics where the
 * runtime exposes them, host-derived fallbacks where it does not).
 */
public final class GPUUtilizationInfo {
  public final int utilizationPercent;
  public final int memUtilizationPercent;

  public GPUUtilizationInfo(int utilizationPercent, int memUtilizationPercent) {
    this.utilizationPercent = utilizationPercent;
    this.memUtilizationPercent = memUtilizationPercent;
  }
}
