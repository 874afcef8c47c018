#include "common/fsutil.h"

#include <errno.h>
#include <sys/stat.h>
#include <unistd.h>

namespace fdfs {

bool MakeDirs(const std::string& path) {
  std::string cur;
  for (size_t i = 0; i < path.size(); ++i) {
    if (path[i] == '/' && !cur.empty()) {
      if (mkdir(cur.c_str(), 0755) != 0 && errno != EEXIST) return false;
    }
    cur.push_back(path[i]);
  }
  if (!cur.empty() && mkdir(cur.c_str(), 0755) != 0 && errno != EEXIST)
    return false;
  return true;
}

bool EnsureParentDirs(const std::string& path) {
  size_t pos = path.find_last_of('/');
  if (pos == std::string::npos) return true;
  return MakeDirs(path.substr(0, pos));
}

}  // namespace fdfs

namespace fdfs {

bool ReadWholeFile(const std::string& path, std::string* out) {
  FILE* f = fopen(path.c_str(), "rb");
  if (f == nullptr) return false;
  out->clear();
  char buf[65536];
  size_t n;
  while ((n = fread(buf, 1, sizeof(buf), f)) > 0) out->append(buf, n);
  bool ok = !ferror(f);
  fclose(f);
  return ok;
}

bool PreadFull(int fd, char* dst, int64_t len, int64_t off) {
  int64_t got = 0;
  while (got < len) {
    ssize_t r = pread(fd, dst + got, static_cast<size_t>(len - got),
                      off + got);
    if (r < 0 && errno == EINTR) continue;
    if (r <= 0) return false;
    got += r;
  }
  return true;
}

}  // namespace fdfs
