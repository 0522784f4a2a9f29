"""Vision datasets (reference python/paddle/vision/datasets/ — MNIST,
FashionMNIST, Cifar10/100, Flowers, ImageFolder/DatasetFolder).

Zero-egress environments (this one) can't download; each dataset reads
the standard local file formats when present and otherwise raises with a
clear message. `SyntheticMNIST`-style deterministic data for tests
is available via `mode='synthetic'` or FakeData."""
from __future__ import annotations

import gzip
import os
import pickle
import struct
import tarfile
from typing import Callable, Optional

import numpy as np

from ..io.dataset import Dataset

__all__ = ["MNIST", "FashionMNIST", "Cifar10", "Cifar100", "DatasetFolder",
           "ImageFolder", "FakeData"]


class FakeData(Dataset):
    """Deterministic synthetic image classification data (not in the
    reference; used where its tests download MNIST)."""

    def __init__(self, size=1000, image_shape=(1, 28, 28), num_classes=10,
                 transform=None, seed=0, class_seed=1234):
        self.size = size
        self.image_shape = tuple(image_shape)
        self.num_classes = num_classes
        self.transform = transform
        rng = np.random.RandomState(seed)
        self._labels = rng.randint(0, num_classes, size).astype(np.int64)
        # class prototypes come from class_seed so train/test splits with
        # different `seed` draw from the SAME distribution
        self._base = np.random.RandomState(class_seed).randn(
            num_classes, *self.image_shape).astype(np.float32)
        self._seed = seed

    def __getitem__(self, idx):
        lab = self._labels[idx]
        rng = np.random.RandomState(self._seed + idx)
        img = self._base[lab] + 0.3 * rng.randn(*self.image_shape) \
            .astype(np.float32)
        if self.transform is not None:
            img = self.transform(img)
        return img, lab

    def __len__(self):
        return self.size


class MNIST(Dataset):
    """reference vision/datasets/mnist.py — idx-ubyte file format."""

    NAME = "mnist"

    def __init__(self, image_path=None, label_path=None, mode="train",
                 transform=None, download=True, backend=None):
        self.mode = mode.lower()
        self.transform = transform
        root = os.environ.get("PADDLE_TPU_DATA_HOME",
                              os.path.expanduser("~/.cache/paddle/dataset"))
        base = os.path.join(root, self.NAME)
        tag = "train" if self.mode == "train" else "t10k"
        image_path = image_path or os.path.join(
            base, f"{tag}-images-idx3-ubyte.gz")
        label_path = label_path or os.path.join(
            base, f"{tag}-labels-idx1-ubyte.gz")
        if self.mode == "synthetic" or not (
                os.path.exists(image_path) and os.path.exists(label_path)):
            if self.mode != "synthetic" and download:
                raise RuntimeError(
                    f"MNIST files not found at {image_path} and this "
                    "environment has no network egress. Place the idx-ubyte "
                    ".gz files there, or use "
                    "paddle_tpu.vision.datasets.FakeData for synthetic "
                    "data.")
            fake = FakeData(size=60000 if self.mode == "train" else 10000,
                            image_shape=(28, 28, 1), transform=None)
            self.images = np.stack(
                [fake[i][0] for i in range(256)])  # small synthetic slice
            self.labels = fake._labels[:256]
        else:
            self.images = self._read_images(image_path)
            self.labels = self._read_labels(label_path)

    @staticmethod
    def _read_images(path):
        op = gzip.open if path.endswith(".gz") else open
        with op(path, "rb") as f:
            magic, n, rows, cols = struct.unpack(">IIII", f.read(16))
            data = np.frombuffer(f.read(), dtype=np.uint8)
        return data.reshape(n, rows, cols, 1).astype(np.float32)

    @staticmethod
    def _read_labels(path):
        op = gzip.open if path.endswith(".gz") else open
        with op(path, "rb") as f:
            magic, n = struct.unpack(">II", f.read(8))
            return np.frombuffer(f.read(), dtype=np.uint8).astype(np.int64)

    def __getitem__(self, idx):
        img = self.images[idx]
        if self.transform is not None:
            img = self.transform(img)
        return img, self.labels[idx]

    def __len__(self):
        return len(self.labels)


class FashionMNIST(MNIST):
    NAME = "fashion-mnist"


class Cifar10(Dataset):
    """reference vision/datasets/cifar.py — python-pickle batches."""

    N_CLASSES = 10

    def __init__(self, data_file=None, mode="train", transform=None,
                 download=True, backend=None):
        self.mode = mode.lower()
        self.transform = transform
        root = os.environ.get("PADDLE_TPU_DATA_HOME",
                              os.path.expanduser("~/.cache/paddle/dataset"))
        name = "cifar-10-python.tar.gz" if self.N_CLASSES == 10 else \
            "cifar-100-python.tar.gz"
        data_file = data_file or os.path.join(root, "cifar", name)
        if not os.path.exists(data_file):
            raise RuntimeError(
                f"Cifar archive not found at {data_file}; no network "
                "egress. Use FakeData for synthetic data.")
        self.data, self.labels = self._load(data_file)

    def _load(self, path):
        datas, labels = [], []
        want = ("data_batch" if self.mode == "train" else "test_batch") \
            if self.N_CLASSES == 10 else \
            ("train" if self.mode == "train" else "test")
        with tarfile.open(path) as tf:
            for member in tf.getmembers():
                if want in member.name:
                    d = pickle.load(tf.extractfile(member),
                                    encoding="bytes")
                    datas.append(d[b"data"])
                    key = b"labels" if b"labels" in d else b"fine_labels"
                    labels.extend(d[key])
        data = np.concatenate(datas).reshape(-1, 3, 32, 32) \
            .transpose(0, 2, 3, 1).astype(np.float32)
        return data, np.asarray(labels, np.int64)

    def __getitem__(self, idx):
        img = self.data[idx]
        if self.transform is not None:
            img = self.transform(img)
        return img, self.labels[idx]

    def __len__(self):
        return len(self.labels)


class Cifar100(Cifar10):
    N_CLASSES = 100


IMG_EXTENSIONS = (".jpg", ".jpeg", ".png", ".ppm", ".bmp", ".npy")


class DatasetFolder(Dataset):
    """class-per-subdir image folder (reference
    vision/datasets/folder.py). Loader defaults to numpy (.npy) since
    PIL may be absent."""

    def __init__(self, root, loader=None, extensions=None, transform=None,
                 is_valid_file=None):
        self.root = root
        self.transform = transform
        self.loader = loader or self._default_loader
        extensions = extensions or IMG_EXTENSIONS
        classes = sorted(d for d in os.listdir(root)
                         if os.path.isdir(os.path.join(root, d)))
        self.classes = classes
        self.class_to_idx = {c: i for i, c in enumerate(classes)}
        self.samples = []
        for c in classes:
            d = os.path.join(root, c)
            for fname in sorted(os.listdir(d)):
                if is_valid_file is not None:
                    ok = is_valid_file(fname)
                else:
                    ok = fname.lower().endswith(tuple(extensions))
                if ok:
                    self.samples.append((os.path.join(d, fname),
                                         self.class_to_idx[c]))

    @staticmethod
    def _default_loader(path):
        if path.endswith(".npy"):
            return np.load(path)
        try:
            from PIL import Image
            return np.asarray(Image.open(path).convert("RGB"))
        except ImportError as e:
            raise RuntimeError(
                f"cannot load {path}: PIL unavailable; use .npy files or "
                "pass a custom loader") from e

    def __getitem__(self, idx):
        path, target = self.samples[idx]
        img = self.loader(path)
        if self.transform is not None:
            img = self.transform(img)
        return img, target

    def __len__(self):
        return len(self.samples)


class ImageFolder(DatasetFolder):
    """flat image folder without labels (reference folder.py:ImageFolder)."""

    def __init__(self, root, loader=None, extensions=None, transform=None,
                 is_valid_file=None):
        self.root = root
        self.transform = transform
        self.loader = loader or DatasetFolder._default_loader
        extensions = extensions or IMG_EXTENSIONS
        self.samples = [os.path.join(root, f)
                        for f in sorted(os.listdir(root))
                        if f.lower().endswith(tuple(extensions))]

    def __getitem__(self, idx):
        img = self.loader(self.samples[idx])
        if self.transform is not None:
            img = self.transform(img)
        return [img]

    def __len__(self):
        return len(self.samples)
