package com.nvidia.spark.rapids.jni.nvml;

/**
 * Device PCIeInfo snapshot (reference nvml/GPUPCIeInfo.java;
 * TPU source: utils/telemetry.py — accelerator metrics where the
 * runtime exposes them, host-derived fallbacks where it does not).
 */
public final class GPUPCIeInfo {
  public final int linkGeneration;
  public final int linkWidth;

  public GPUPCIeInfo(int linkGeneration, int linkWidth) {
    this.linkGeneration = linkGeneration;
    this.linkWidth = linkWidth;
  }
}
