#include "storage/store.h"

#include <errno.h>
#include <fcntl.h>
#include <string.h>
#include <sys/stat.h>
#include <unistd.h>

#include <cstdio>

#include "common/log.h"

namespace fdfs {

bool StoreManager::Init(const StorageConfig& cfg, std::string* error) {
  paths_ = cfg.store_paths;
  subdir_count_ = cfg.subdir_count_per_path;
  for (const std::string& p : paths_) {
    std::string data = p + "/data";
    std::string flag = data + "/.data_init_flag";
    struct stat st;
    if (stat(flag.c_str(), &st) == 0) continue;  // already initialized
    any_fresh_ = true;
    // Pre-create the two-level fan-out (reference:
    // storage_make_data_dirs()).
    // std::string: a store path may be longer than any fixed buffer.
    for (int i = 0; i < subdir_count_; ++i) {
      char hex[8];
      std::snprintf(hex, sizeof(hex), "/%02X", i);
      const std::string sub = data + hex;
      if (!MakeDirs(sub)) {
        *error = "mkdir " + sub + ": " + strerror(errno);
        return false;
      }
      for (int j = 0; j < subdir_count_; ++j) {
        std::snprintf(hex, sizeof(hex), "/%02X", j);
        const std::string sub2 = sub + hex;
        if (mkdir(sub2.c_str(), 0755) != 0 && errno != EEXIST) {
          *error = "mkdir " + sub2 + ": " + strerror(errno);
          return false;
        }
      }
    }
    if (!MakeDirs(p + "/tmp")) {
      *error = "mkdir " + p + "/tmp failed";
      return false;
    }
    int fd = open(flag.c_str(), O_CREAT | O_WRONLY, 0644);
    if (fd < 0) {
      *error = "create " + flag + " failed";
      return false;
    }
    close(fd);
    FDFS_LOG_INFO("initialized data dirs under %s (%d^2 subdirs)", p.c_str(),
                  subdir_count_);
  }
  return true;
}

int StoreManager::PickStorePath() {
  // Round-robin across nio work threads; wrap with a plain mod (the
  // counter only feeds distribution, exact fairness does not matter).
  return static_cast<int>(
      next_path_.fetch_add(1, std::memory_order_relaxed) %
      static_cast<uint64_t>(paths_.size()));
}

std::string StoreManager::NewTmpPath(int spi) {
  char buf[64];
  std::snprintf(buf, sizeof(buf), "/tmp/upload_%d_%u", getpid(),
                tmp_seq_.fetch_add(1));
  return paths_[static_cast<size_t>(spi)] + buf;
}

}  // namespace fdfs
