"""Validator-dir discipline (VERDICT r4 row 35): on-disk keystore homes,
a definitions manifest, and LOCKFILES that stop two processes signing
with the same keys (common/validator_dir + common/lockfile +
initialized_validators.rs)."""

import os
import subprocess
import sys

import pytest

from lighthouse_tpu.crypto import keystore as ks
from lighthouse_tpu.crypto.bls.api import SecretKey
from lighthouse_tpu.validator.validator_dir import (
    LockfileError,
    ValidatorDirManager,
)


def _keystore(i: int) -> dict:
    sk = SecretKey(1000 + i)
    return ks.encrypt(
        sk.to_bytes(), "pw", kdf="pbkdf2",
        pubkey=sk.public_key().to_bytes(),
    )


def test_create_and_manifest(tmp_path):
    mgr = ValidatorDirManager(str(tmp_path))
    v = mgr.create(_keystore(0))
    assert os.path.exists(v.keystore_path)
    defs = mgr.definitions()
    assert len(defs) == 1 and defs[0]["enabled"]
    # re-create same pubkey: no duplicate definition
    mgr.create(_keystore(0))
    assert len(mgr.definitions()) == 1


def test_lock_excludes_second_holder(tmp_path):
    mgr = ValidatorDirManager(str(tmp_path))
    store = _keystore(1)
    mgr.create(store)
    v1 = mgr.open_validator(store["pubkey"])
    with pytest.raises(LockfileError):
        # same-process second open models a second VC: the pid is alive
        mgr2 = ValidatorDirManager(str(tmp_path))
        mgr2.open_validator(store["pubkey"])
    v1.lock.release()
    # once released, a new holder may take it
    v2 = mgr.open_validator(store["pubkey"])
    v2.lock.release()


def test_stale_lock_reclaimed(tmp_path):
    mgr = ValidatorDirManager(str(tmp_path))
    store = _keystore(2)
    v = mgr.create(store)
    # a dead process's pid in the lockfile must not brick the keys: a
    # fresh interpreter (not a fork of this one and its threads) takes
    # the lock and dies WITHOUT releasing it
    hold = ("import os, sys; "
            "from lighthouse_tpu.validator.validator_dir import Lockfile; "
            "Lockfile(sys.argv[1]).acquire(); os._exit(0)")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", hold, v.lock.path],
                          cwd=root, capture_output=True, text=True,
                          timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert os.path.exists(v.lock.path)
    v2 = mgr.open_validator(store["pubkey"])  # reclaims
    v2.lock.release()


def test_open_enabled_all_or_nothing(tmp_path):
    mgr = ValidatorDirManager(str(tmp_path))
    s1, s2 = _keystore(3), _keystore(4)
    mgr.create(s1)
    mgr.create(s2)
    # someone holds validator 2's lock
    held = mgr.open_validator(s2["pubkey"])
    with pytest.raises(LockfileError):
        ValidatorDirManager(str(tmp_path)).open_enabled()
    # validator 1's lock must have been rolled back
    v1 = mgr.open_validator(s1["pubkey"])
    v1.lock.release()
    held.lock.release()
    # disabled definitions are not opened
    mgr.set_enabled(s2["pubkey"], False)
    opened = mgr.open_enabled()
    assert len(opened) == 1
    for v in opened:
        v.lock.release()


def test_decrypt_enabled_feeds_signing_keys(tmp_path):
    mgr = ValidatorDirManager(str(tmp_path))
    store = _keystore(5)
    mgr.create(store)
    out = mgr.decrypt_enabled("pw")
    assert len(out) == 1
    pubkey, sk, vdir = out[0]
    assert pubkey.hex() == store["pubkey"].removeprefix("0x")
    assert sk.public_key().to_bytes() == pubkey
    vdir.lock.release()


def test_cli_validator_manager_installs_dirs(tmp_path):
    from lighthouse_tpu.cli import main

    rc = main([
        "validator-manager", "create", "--count", "2",
        "--wallet-password", "wp", "--keystore-password", "kp",
        "--seed-hex", "11" * 32,
        "--output-dir", str(tmp_path),
    ])
    assert rc == 0
    mgr = ValidatorDirManager(str(tmp_path))
    assert len(mgr.definitions()) == 2
    for v in mgr.open_enabled():
        assert os.path.exists(v.keystore_path)
        v.lock.release()
