package com.nvidia.spark.rapids.jni.nvml;

/**
 * Device TemperatureInfo snapshot (reference nvml/GPUTemperatureInfo.java;
 * TPU source: utils/telemetry.py — accelerator metrics where the
 * runtime exposes them, host-derived fallbacks where it does not).
 */
public final class GPUTemperatureInfo {
  public final int temperatureC;
  public final int slowdownThresholdC;

  public GPUTemperatureInfo(int temperatureC, int slowdownThresholdC) {
    this.temperatureC = temperatureC;
    this.slowdownThresholdC = slowdownThresholdC;
  }
}
