"""Scalar SRTP oracle for the benchmark's clients (OpenSSL through
`cryptography`, stdlib `hmac`/`hashlib`; RFC 3711, RFC 7714).

Copied from `chip_smoke.py` so that the yardstick does not move when
the smoke does.  It shares no code with the device path and imports
nothing of `libjitsi_tpu`: what a client accepts under it is evidence
about the bridge alone.  The generator children import this module and
never JAX.
"""

from __future__ import annotations

import functools
import hashlib
import hmac as hmac_mod

from cryptography.hazmat.primitives.ciphers import (Cipher, algorithms,
                                                    modes)
from cryptography.hazmat.primitives.ciphers.aead import AESGCM
from cryptography.exceptions import InvalidTag


def _aes_ctr(key: bytes, iv16: bytes, data: bytes) -> bytes:
    enc = Cipher(algorithms.AES(key), modes.CTR(iv16)).encryptor()
    return enc.update(data) + enc.finalize()


def _kdf(mk: bytes, ms: bytes, label: int, n: int) -> bytes:
    x = int.from_bytes(ms.ljust(14, b"\x00"), "big") ^ (label << 48)
    return _aes_ctr(mk, (x << 16).to_bytes(16, "big"), b"\x00" * n)


@functools.lru_cache(maxsize=None)
def _cm_session(mk: bytes, ms: bytes):
    """(cipher key, auth key, salt as int) of one master key pair."""
    return (_kdf(mk, ms, 0, len(mk)), _kdf(mk, ms, 1, 20),
            int.from_bytes(_kdf(mk, ms, 2, 14), "big"))


@functools.lru_cache(maxsize=None)
def _gcm_session(mk: bytes, ms: bytes):
    """(AEAD object, salt as int) of one master key pair."""
    return (AESGCM(_kdf(mk, ms, 0, len(mk))),
            int.from_bytes(_kdf(mk, ms, 2, 12), "big"))


def payload_off(pkt: bytes) -> int:
    off = 12 + 4 * (pkt[0] & 0x0F)
    if pkt[0] & 0x10:                       # RFC 3550 5.3.1 extension
        off += 4 + 4 * int.from_bytes(pkt[off + 2:off + 4], "big")
    return off


def _cm_iv(ksalt: int, pkt: bytes, index: int) -> bytes:
    ssrc = int.from_bytes(pkt[8:12], "big")
    return ((ksalt << 16) ^ (ssrc << 64) ^ (index << 16)).to_bytes(16, "big")


def protect_cm(mk: bytes, ms: bytes, pkt: bytes, index: int,
               tag_len: int = 10) -> bytes:
    """AES_CM_128_HMAC_SHA1 protect of one RTP packet (RFC 3711 3.1)."""
    ke, ka, ksalt = _cm_session(mk, ms)
    off = payload_off(pkt)
    ct = pkt[:off] + _aes_ctr(ke, _cm_iv(ksalt, pkt, index), pkt[off:])
    tag = hmac_mod.new(ka, ct + (index >> 16).to_bytes(4, "big"),
                       hashlib.sha1).digest()
    return ct + tag[:tag_len]


def unprotect_cm(mk: bytes, ms: bytes, wire: bytes, index: int,
                 tag_len: int = 10):
    """Inverse of `protect_cm`; None when the tag does not verify."""
    ke, ka, ksalt = _cm_session(mk, ms)
    ct, tag = wire[:-tag_len], wire[-tag_len:]
    want = hmac_mod.new(ka, ct + (index >> 16).to_bytes(4, "big"),
                        hashlib.sha1).digest()[:tag_len]
    if not hmac_mod.compare_digest(tag, want):
        return None
    off = payload_off(ct)
    return ct[:off] + _aes_ctr(ke, _cm_iv(ksalt, ct, index), ct[off:])


def _gcm_iv(ks: int, pkt: bytes, index: int) -> bytes:
    ssrc = int.from_bytes(pkt[8:12], "big")
    return (ks ^ (ssrc << 48) ^ index).to_bytes(12, "big")


def protect_gcm(mk: bytes, ms: bytes, pkt: bytes, index: int) -> bytes:
    """AEAD_AES_128_GCM protect of one RTP packet (RFC 7714 8-9)."""
    aead, ks = _gcm_session(mk, ms)
    off = payload_off(pkt)
    return pkt[:off] + aead.encrypt(_gcm_iv(ks, pkt, index), pkt[off:],
                                    pkt[:off])


def unprotect_gcm(mk: bytes, ms: bytes, wire: bytes, index: int):
    """Inverse of `protect_gcm`; None when the tag does not verify."""
    aead, ks = _gcm_session(mk, ms)
    off = payload_off(wire)
    try:
        return wire[:off] + aead.decrypt(_gcm_iv(ks, wire, index),
                                         wire[off:], wire[:off])
    except InvalidTag:
        return None


#: suite name (as the configuration file spells it) -> (protect,
#: unprotect, master salt length, bytes a protected packet grows by)
SUITES = {
    "AES_CM_128_HMAC_SHA1_80": (protect_cm, unprotect_cm, 14, 10),
    "AEAD_AES_128_GCM": (protect_gcm, unprotect_gcm, 12, 16),
}
