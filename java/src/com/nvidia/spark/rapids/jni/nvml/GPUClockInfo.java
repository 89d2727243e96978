package com.nvidia.spark.rapids.jni.nvml;

/**
 * Device ClockInfo snapshot (reference nvml/GPUClockInfo.java;
 * TPU source: utils/telemetry.py — accelerator metrics where the
 * runtime exposes them, host-derived fallbacks where it does not).
 */
public final class GPUClockInfo {
  public final int graphicsClockMhz;
  public final int memClockMhz;

  public GPUClockInfo(int graphicsClockMhz, int memClockMhz) {
    this.graphicsClockMhz = graphicsClockMhz;
    this.memClockMhz = memClockMhz;
  }
}
