package com.nvidia.spark.rapids.jni.nvml;

/**
 * Device PowerInfo snapshot (reference nvml/GPUPowerInfo.java;
 * TPU source: utils/telemetry.py — accelerator metrics where the
 * runtime exposes them, host-derived fallbacks where it does not).
 */
public final class GPUPowerInfo {
  public final int powerUsageWatts;
  public final int powerLimitWatts;

  public GPUPowerInfo(int powerUsageWatts, int powerLimitWatts) {
    this.powerUsageWatts = powerUsageWatts;
    this.powerLimitWatts = powerLimitWatts;
  }
}
