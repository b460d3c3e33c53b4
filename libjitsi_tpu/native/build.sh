#!/bin/sh
# Build the native UDP engine (C ABI shared lib consumed via ctypes).
#
#   ./build.sh          optimized build -> libudp_engine.so
#   ./build.sh tsan     ThreadSanitizer build -> libudp_engine_tsan.so
#                       (SURVEY section 5 race detection: the reference
#                       ships no sanitizer builds; ours gates the C++
#                       I/O engine)
#   ./build.sh asan     AddressSanitizer build -> libudp_engine_asan.so
#
# Select a sanitized library at runtime with
#   LIBJITSI_TPU_UDP_ENGINE=/path/to/libudp_engine_tsan.so
# dlopen of a sanitized lib needs its runtime preloaded into the
# (uninstrumented) Python interpreter:
#   LD_PRELOAD=/lib/x86_64-linux-gnu/libtsan.so.2   (tsan build)
#   LD_PRELOAD=$(g++ -print-file-name=libasan.so)   (asan build;
#     add ASAN_OPTIONS=detect_leaks=0 — CPython itself trips LSan)
set -e
cd "$(dirname "$0")"

# io_uring detection: prefer the kernel UAPI header (liburing is NOT
# required — the engine speaks raw io_uring_setup/enter syscalls).
# Without the header, the same .so still builds with every udp_uring_*
# entry point stubbed to ENOSYS; the Python probe then keeps the
# recvmmsg engine with a bit-identical accept set.
URING_FLAGS=""
if [ -e /usr/include/linux/io_uring.h ] || \
   [ -e /usr/include/liburing.h ]; then
  URING_FLAGS="-DHAVE_IO_URING"
fi

# Every library is linked under a private name and renamed into place:
# a rename is atomic, so several processes that find the .so missing at
# once (xdist workers in a fresh checkout) each install a whole file,
# and a process that has the old one mapped keeps its inode.
link() {   # link <output.so> <g++ arguments...>
  out="$1"; shift
  g++ "$@" -o "$out.$$" && mv -f "$out.$$" "$out"
}

# C++ OpenSSL differential oracle (no dev headers in the image: the
# .cpp declares the stable EVP ABI; link the versioned lib directly)
build_oracle() {
  link libcrypto_oracle.so -O2 -Wall -shared -fPIC \
      crypto_oracle.cpp /usr/lib/x86_64-linux-gnu/libcrypto.so.3
}

case "${1:-}" in
  tsan)
    link libudp_engine_tsan.so -O1 -g -Wall -pthread $URING_FLAGS \
        -fsanitize=thread -shared -fPIC udp_engine.cpp
    echo "built $(pwd)/libudp_engine_tsan.so" ;;
  asan)
    link libudp_engine_asan.so -O1 -g -Wall -pthread $URING_FLAGS \
        -fsanitize=address -shared -fPIC udp_engine.cpp
    echo "built $(pwd)/libudp_engine_asan.so" ;;
  oracle)
    build_oracle
    echo "built $(pwd)/libcrypto_oracle.so" ;;
  *)
    link libudp_engine.so -O2 -Wall -pthread $URING_FLAGS -shared -fPIC \
        udp_engine.cpp
    # oracle is best-effort here: a box without libcrypto.so.3 still
    # gets the UDP engine (tests needing the oracle build it
    # explicitly via `build.sh oracle` and fail loudly there)
    if build_oracle 2>/dev/null; then
      echo "built $(pwd)/libudp_engine.so + libcrypto_oracle.so"
    else
      echo "built $(pwd)/libudp_engine.so (no libcrypto.so.3: oracle skipped)"
    fi ;;
esac
