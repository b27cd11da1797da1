/* Fused image normalize+pad kernels for the host input pipeline.
 *
 * The loader's numpy normalize ((img - mean) / std) and zero-pad stages
 * hold the GIL and walk the image twice. These kernels do both in one
 * pass, called through ctypes (which releases the GIL for the duration),
 * so loader workers run in parallel, and they write a destination the
 * caller owns: the training loader passes each image's row of a reused
 * batch buffer (data/loader.py), so no page of a batch is new to the
 * process.
 *
 * Reference lineage: rcnn/io/image.py::transform + tensor_vstack padding
 * (pure numpy there; the reference's native layer was the CUDA ops, not
 * IO — this is TPU-era surface, where one host must keep up with four
 * chips that consume 70 img/s each).
 *
 * Layout: HWC, C=3, RGB. dst is (ph, pw, 3) float32, fully written
 * (image region normalized, remainder zeroed). src strides are
 * contiguous rows of w*3 elements.
 */

#include <stddef.h>
#include <string.h>

void normalize_pad_u8(const unsigned char *src, long h, long w,
                      float *dst, long ph, long pw,
                      const float *mean, const float *inv_std) {
  const float m0 = mean[0], m1 = mean[1], m2 = mean[2];
  const float s0 = inv_std[0], s1 = inv_std[1], s2 = inv_std[2];
  for (long y = 0; y < ph; ++y) {
    float *drow = dst + (size_t)y * pw * 3;
    if (y < h) {
      const unsigned char *srow = src + (size_t)y * w * 3;
      for (long x = 0; x < w; ++x) {
        drow[3 * x + 0] = ((float)srow[3 * x + 0] - m0) * s0;
        drow[3 * x + 1] = ((float)srow[3 * x + 1] - m1) * s1;
        drow[3 * x + 2] = ((float)srow[3 * x + 2] - m2) * s2;
      }
      if (pw > w)
        memset(drow + 3 * w, 0, sizeof(float) * 3 * (size_t)(pw - w));
    } else {
      memset(drow, 0, sizeof(float) * 3 * (size_t)pw);
    }
  }
}

void normalize_pad_f32(const float *src, long h, long w,
                       float *dst, long ph, long pw,
                       const float *mean, const float *inv_std) {
  const float m0 = mean[0], m1 = mean[1], m2 = mean[2];
  const float s0 = inv_std[0], s1 = inv_std[1], s2 = inv_std[2];
  for (long y = 0; y < ph; ++y) {
    float *drow = dst + (size_t)y * pw * 3;
    if (y < h) {
      const float *srow = src + (size_t)y * w * 3;
      for (long x = 0; x < w; ++x) {
        drow[3 * x + 0] = (srow[3 * x + 0] - m0) * s0;
        drow[3 * x + 1] = (srow[3 * x + 1] - m1) * s1;
        drow[3 * x + 2] = (srow[3 * x + 2] - m2) * s2;
      }
      if (pw > w)
        memset(drow + 3 * w, 0, sizeof(float) * 3 * (size_t)(pw - w));
    } else {
      memset(drow, 0, sizeof(float) * 3 * (size_t)pw);
    }
  }
}

/* Horizontally mirrored variant (the loader's flip path): writes the
 * image region x-reversed, so flip + normalize + pad is ONE pass too. */
void normalize_pad_u8_flip(const unsigned char *src, long h, long w,
                           float *dst, long ph, long pw,
                           const float *mean, const float *inv_std) {
  const float m0 = mean[0], m1 = mean[1], m2 = mean[2];
  const float s0 = inv_std[0], s1 = inv_std[1], s2 = inv_std[2];
  for (long y = 0; y < ph; ++y) {
    float *drow = dst + (size_t)y * pw * 3;
    if (y < h) {
      const unsigned char *srow = src + (size_t)y * w * 3;
      for (long x = 0; x < w; ++x) {
        const unsigned char *sp = srow + 3 * (w - 1 - x);
        drow[3 * x + 0] = ((float)sp[0] - m0) * s0;
        drow[3 * x + 1] = ((float)sp[1] - m1) * s1;
        drow[3 * x + 2] = ((float)sp[2] - m2) * s2;
      }
      if (pw > w)
        memset(drow + 3 * w, 0, sizeof(float) * 3 * (size_t)(pw - w));
    } else {
      memset(drow, 0, sizeof(float) * 3 * (size_t)pw);
    }
  }
}
