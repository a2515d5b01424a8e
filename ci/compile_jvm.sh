#!/usr/bin/env bash
#
# JVM plugin compile gate — the analog of the reference's sbt build of
# jvm/ (its Plugin + wrappers + SparkRapidsMLSuite).  Behavior, best
# toolchain first:
#
#   * sbt present            -> `sbt compile` (full typecheck against the
#     resolved provided deps), hard gate.
#   * scalac present         -> SYNTAX-ONLY gate (-Ystop-after:parser):
#     the full typecheck needs the Spark provided jars, which scalac
#     alone cannot resolve.  Type-invalid Scala passes this stage; the
#     echo says so.
#   * neither, but network   -> opportunistic fetch: coursier -> scalac,
#     then the syntax gate (first networked environment produces a real
#     compile log).
#   * air-gapped, no JVM     -> the structural gate
#     (ci/jvm_structural_check.py): brace balancing, ServiceLoader
#     registration resolution, Plugin target resolution, operator
#     dispatchability, ModelBuilder field inventory.  The runtime half
#     (field-by-field worker golden tests) runs in the pytest suite
#     (tests/test_jvm_protocol.py).
#
set -euo pipefail
cd "$(dirname "$0")/.."

fetch_scalac() {
    # coursier is a single self-contained launcher; it bootstraps a JVM
    # (--jvm) and scalac without root.  Any failure falls through.
    command -v scalac >/dev/null 2>&1 && return 0
    timeout 10 python -c "import socket; socket.create_connection(('github.com', 443), timeout=5)" 2>/dev/null || return 1
    echo "== jvm: network present, fetching coursier + scala toolchain =="
    mkdir -p /tmp/cs-bin
    (curl -fsSL -o /tmp/cs-bin/cs.gz \
        "https://github.com/coursier/coursier/releases/latest/download/cs-x86_64-pc-linux.gz" \
        && gunzip -f /tmp/cs-bin/cs.gz && chmod +x /tmp/cs-bin/cs \
        && /tmp/cs-bin/cs install scalac scala --install-dir /tmp/cs-bin --jvm temurin:17) \
        || return 1
    export PATH="/tmp/cs-bin:$PATH"
    command -v scalac >/dev/null 2>&1
}

if command -v sbt >/dev/null 2>&1; then
    echo "== jvm: sbt compile (full typecheck) =="
    (cd jvm && sbt -batch compile) | tee /tmp/jvm_compile.log
elif command -v scalac >/dev/null 2>&1 || fetch_scalac; then
    echo "== jvm: scalac SYNTAX-ONLY gate (-Ystop-after:parser; full =="
    echo "== typecheck needs the Spark provided jars, absent here)   =="
    scalac -Ystop-after:parser -d /tmp/jvm_classes \
        $(find jvm/src/main/scala -name '*.scala') | tee /tmp/jvm_compile.log
    # preserve the first real parse as a committed artifact
    { echo "scalac $(scalac -version 2>&1)"; echo "date $(date -u +%FT%TZ)";
      echo "gate -Ystop-after:parser PASSED on:";
      find jvm/src/main/scala -name '*.scala'; } > jvm/COMPILE_LOG.txt
else
    echo "== jvm: no JVM toolchain, no network — structural gate =="
    JAX_PLATFORMS=cpu python ci/jvm_structural_check.py
fi
echo "JVM GATE PASSED"
