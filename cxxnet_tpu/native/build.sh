#!/bin/sh
# Build the native libraries: sh build.sh [native|capi|all]  (default all)
#
# native: the data-plane library (JPEG decode + record scan) — mirrors the
#   role of the reference's Makefile USE_OPENCV_DECODER=0 path (libjpeg
#   fallback decoder, src/utils/decoder.h). Built for the generic target
#   (no -march=native): the .so is git-ignored and may be copied with the
#   tree to a machine with another CPU. io/native.py runs this target on
#   the machine that uses the library when it is missing or stale.
# capi: the C ABI (reference wrapper/cxxnet_wrapper.h analog): embeds
#   CPython and delegates to cxxnet_tpu.capi_bridge. Optional: skipped
#   (without failing) when the CPython embed toolchain is missing.
set -e
cd "$(dirname "$0")"
what="${1:-all}"

if [ "$what" = native ] || [ "$what" = all ]; then
  # built beside its final name and moved into place: a concurrent loader
  # never sees a half-written library
  tmp="libcxxnet_native.so.$$.tmp"
  g++ -O3 -fPIC -shared -o "$tmp" decode.cc -ljpeg || { rm -f "$tmp"; exit 1; }
  mv -f "$tmp" libcxxnet_native.so
  echo "built $(pwd)/libcxxnet_native.so"
fi

if [ "$what" = capi ] || [ "$what" = all ]; then
  if EMBED_FLAGS=$(python3-config --includes --ldflags --embed 2>/dev/null); then
    g++ -O3 -fPIC -shared -o libcxxnet_capi.so capi.cc ${EMBED_FLAGS}
    echo "built $(pwd)/libcxxnet_capi.so"
  else
    echo "skipped libcxxnet_capi.so (no python3-config --embed support)"
  fi
fi
